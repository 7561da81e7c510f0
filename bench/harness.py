"""Finds a cell's pieces by name and builds what the program is called with.

A cell ``<config>.<traffic>`` in ``BENCHMARK.json`` names three files,
each found by its name alone, so a later cell is new files plus new
entries and no edit:

  configs/<config>.json   the deployment: fleet, EET shape, load, dynamics;
                          ``"family"`` names its family (default
                          ``independent``)
  traffic/<traffic>.json  the call: entry path, replicas, chunk, policies,
                          arrival processes, streaming window, devices
  paths/<path>.py         the driver of one entry path (``call``, ``warm``)

and each per-layer metric ``<name>`` has its reader in
``metrics/<name>.py`` (``read(ctx) -> float | None``).

A family is a kind of deployment: the tasks' shape (independent tasks,
workflows with precedence, gang jobs, ...) and the semantics the
reference must hold the program to.  ``families/<family>.py`` brings
everything of it that the harness and the check call (``FAMILY_API``):

  axes(config, traffic) -> dict        the cell's grid axes
  make_spec(config, traffic, seed)     the ``ExperimentSpec`` of one call;
                                       imports the program, the rest
                                       of the module only numpy
  replica_policies(axes, R) -> (R,)    each replica's index into
                                       ``axes["policies"]``: the
                                       program's layout of a call
  draw(config, axes, seed, r)          replica ``r``'s inputs and policy,
                                       drawn again from the seed
  simulate(inputs, policy, window=None, precision=...) -> row
                                       the plain reference's report row
  COUNT_COLUMNS, VALUE_COLUMNS         the row's columns the check
                                       compares exactly / relatively

A new family brings that module and a test that its reference agrees
with the program's own oracle (``core/ref_engine.py``) on small
instances, as ``tests/test_bench_reference.py`` does for
``independent``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(ROOT, "results", "bench")
FAMILY_API = ("axes", "make_spec", "replica_policies", "draw", "simulate",
              "COUNT_COLUMNS", "VALUE_COLUMNS")


def load_benchmark(root: str | None = None) -> dict:
    with open(os.path.join(root or ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        return json.load(fh)


def load_json(kind: str, name: str, bench_dir: str | None = None) -> dict:
    with open(os.path.join(bench_dir or BENCH_DIR, kind, f"{name}.json"),
              encoding="utf-8") as fh:
        return json.load(fh)


def load_module(kind: str, name: str, bench_dir: str | None = None):
    path = os.path.join(bench_dir or BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def _family_at(bench_dir: str, name: str):
    mod = load_module("families", name, bench_dir)
    missing = [k for k in FAMILY_API if not hasattr(mod, k)]
    if missing:
        raise AttributeError(f"family {name!r} lacks {missing}")
    return mod


def family(config: dict, bench_dir: str | None = None):
    """The family module the configuration names, loaded once a process."""
    return _family_at(bench_dir or BENCH_DIR,
                      config.get("family", "independent"))


def resolve(workload: str, bench_dir: str | None = None) -> dict:
    """Everything one cell needs: its entry, config, family, traffic, path
    driver, and its metrics with their readers (per-layer) and specs."""
    bench_dir = bench_dir or BENCH_DIR
    bench = load_benchmark(os.path.dirname(bench_dir))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    config = load_json("configs", cell["config"], bench_dir)
    traffic = load_json("traffic", cell["traffic"], bench_dir)
    mine = [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])]
    return {
        "cell": cell,
        "config": config,
        "family": family(config, bench_dir),
        "traffic": traffic,
        "path": load_module("paths", traffic["path"], bench_dir),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": mine,
        "readers": {m["name"]: load_module("metrics", m["name"], bench_dir)
                    for m in mine},
    }


def make_spec(config: dict, traffic: dict, seed: int):
    """The ``ExperimentSpec`` one call of the cell runs, for ``seed``."""
    return family(config).make_spec(config, traffic, seed)
