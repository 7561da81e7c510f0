"""BENCHMARK.json holds to the benchmark's contract, and every cell and
metric it names resolves to its files."""
import json
import os
import re

import pytest

from bench import harness as H

BENCH = H.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_limits():
    assert set(BENCH) == KEYS
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(_line(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(H.ROOT, p))
    for w in BENCH["command"][1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in BENCH["paths"])
    assert os.path.getsize(os.path.join(H.ROOT, "BENCHMARK.json")) <= 65536


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    res = H.resolve(cell)
    w = res["cell"]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert w["chips"] in (1, 4) and _line(w["why"])
    assert callable(res["path"].call) and callable(res["path"].warm)
    assert res["traffic"]["devices"] == w["chips"]
    assert {m["name"] for m in res["end_to_end"]} >= {"setup_s"}
    assert len(res["end_to_end"]) >= 2 and res["per_layer"]
    for name, reader in res["readers"].items():
        assert callable(reader.read)
        assert reader.read({"traffic": res["traffic"]}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_cell_family_exposes_the_interface(cell):
    fam = H.resolve(cell)["family"]
    assert H.FAMILY_API == ("axes", "make_spec", "replica_policies", "draw",
                            "simulate", "COUNT_COLUMNS", "VALUE_COLUMNS")
    for name in H.FAMILY_API[:5]:
        assert callable(getattr(fam, name)), name
    for name in H.FAMILY_API[5:]:
        cols = getattr(fam, name)
        assert isinstance(cols, tuple) and cols, name
        assert all(isinstance(c, str) for c in cols)


def test_names_units_and_entries():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(H.ROOT, c["file"]), encoding="utf-8") as fh:
            assert json.load(fh)["reduced"] == c["reduced"]
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        names.append(m["name"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


def test_every_config_is_used():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
