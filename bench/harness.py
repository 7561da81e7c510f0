"""Finds a cell's pieces by name and builds what the program is called with.

A cell ``<config>.<traffic>`` in ``BENCHMARK.json`` names three files,
each found by its name alone, so a later cell is new files plus new
entries and no edit:

  configs/<config>.json   the deployment: fleet, EET shape, load, dynamics
  traffic/<traffic>.json  the call: entry path, replicas, chunk, policies,
                          arrival processes, streaming window, devices
  paths/<path>.py         the driver of one entry path (``call``, ``warm``)

and each per-layer metric ``<name>`` has its reader in
``metrics/<name>.py`` (``read(ctx) -> float | None``).
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(ROOT, "results", "bench")


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


def resolve(workload: str, bench_dir: str | None = None) -> dict:
    """Everything one cell needs: its entry, config, traffic, path driver,
    and its metrics with their readers (per-layer) and specs."""
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
    from bench.inputs import cell_axes
    from repro.launch import experiment as X
    axes = cell_axes(config, traffic)
    return X.ExperimentSpec(
        n_replicas=traffic["replicas"],
        fleet=X.FleetAxis(config["n_machines"], config["n_machine_types"]),
        workload=X.WorkloadAxis(config["n_tasks"],
                                n_task_types=config["n_task_types"],
                                rate=config["rate"],
                                arrivals=tuple(axes["arrivals"]),
                                streaming=traffic.get("streaming")),
        scenario=X.ScenarioAxis(fail_rates=tuple(axes["fail_rates"]),
                                dvfs_states=tuple(axes["dvfs_states"]),
                                spot_frac=axes["spot_frac"],
                                mttr=axes["mttr"],
                                n_intervals=axes["n_intervals"]),
        policy=X.PolicyAxis(tuple(axes["policies"])),
        seed=seed)
