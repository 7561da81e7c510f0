"""A later cell is data: a new configuration, traffic file and metric
reader, plus new entries in BENCHMARK.json, load with no file that is
already there edited."""
import hashlib
import json
import os

import pytest

from bench import harness as H
from bench.tests import tiny


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def extended(tmp_path):
    bench = tiny.make(str(tmp_path))
    before = _digests(bench)
    with open(os.path.join(bench, "configs", "tiny-fleet.json"), "w") as fh:
        json.dump({"n_tasks": 30, "n_machines": 3, "n_task_types": 3,
                   "n_machine_types": 3, "rate": 3.0, "reduced": [],
                   "scenario": {"fail_rates": [0.0], "dvfs_states":
                                ["nominal"], "spot_frac": 0.0, "mttr": 4.0,
                                "n_intervals": 1}}, fh)
    with open(os.path.join(bench, "traffic", "pairs.json"), "w") as fh:
        json.dump({"path": "monolithic", "replicas": 4, "devices": 1,
                   "policies": ["mct", "edf_mct"], "arrivals": ["bursty"],
                   "check_per_policy": 1}, fh)
    with open(os.path.join(bench, "metrics", "calls_traced.py"), "w") as fh:
        fh.write("def read(ctx):\n    return ctx.get('n_calls')\n")
    b = H.load_benchmark(str(tmp_path))
    b["configs"].append({"name": "tiny-fleet", "source": "test",
                         "file": "bench/configs/tiny-fleet.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-fleet.pairs", "config": "tiny-fleet",
                           "traffic": "pairs", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "calls_traced", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "device", "moves": "tasks_per_s",
                           "workloads": ["tiny-fleet.pairs"]})
    with open(os.path.join(tmp_path, "BENCHMARK.json"), "w") as fh:
        json.dump(b, fh)
    return bench, before


def test_new_cell_loads_without_edits(extended):
    bench, before = extended
    res = H.resolve("tiny-fleet.pairs", bench_dir=bench)
    after = _digests(bench)
    assert {k: after[k] for k in before} == before
    assert res["readers"]["calls_traced"].read({"n_calls": 3}) == 3
    assert [m["name"] for m in res["per_layer"]][-1] == "calls_traced"
    spec = H.make_spec(res["config"], res["traffic"], seed=5)
    assert spec.n_replicas == 4 and spec.policy.policies == ("mct",
                                                             "edf_mct")
    assert spec.workload.arrivals == ("bursty",)
    # the metric that names its cells stays out of the others
    other = H.resolve("braun512x16.grid", bench_dir=bench)
    assert "calls_traced" not in other["readers"]
