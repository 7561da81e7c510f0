"""A copy of the benchmark with every cell cut to a size the CPU runs in
seconds, for driving ``run.py`` end to end in the tests."""
import json
import os
import shutil

from bench import harness as H

CONFIGS = {"braun512x16": dict(n_tasks=40, n_machines=5, n_task_types=40,
                               n_machine_types=5, rate=4.0)}
#: cells whose files are in bench/ but not yet in BENCHMARK.json (PERF.md
#: Open questions): the tiny copy runs them too, so every path driver runs
PROSPECTIVE = [
    {"name": "braun512x16.stream", "config": "braun512x16",
     "traffic": "stream", "chips": 1, "why": "streaming window"},
    {"name": "braun512x16.grid4", "config": "braun512x16",
     "traffic": "grid4", "chips": 1, "why": "sharded grid"},
]
TRAFFIC = {"grid": dict(replicas=80, chunk=40),
           "stream": dict(replicas=80, streaming=12),
           "single": {},
           "grid4": dict(replicas=80, chunk=40, devices=1)}


def make(dst: str) -> str:
    """Copy ``bench/`` and ``BENCHMARK.json`` under ``dst``, cut to size;
    -> the copy's bench directory."""
    bench = os.path.join(dst, "bench")
    shutil.copytree(H.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = H.load_benchmark()
    have = {w["name"] for w in b["workloads"]}
    b["workloads"] += [w for w in PROSPECTIVE if w["name"] not in have]
    for w in b["workloads"]:
        w["chips"] = 1
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(b, fh)
    for kind, table in (("configs", CONFIGS), ("traffic", TRAFFIC)):
        for name, kw in table.items():
            p = os.path.join(bench, kind, f"{name}.json")
            with open(p) as fh:
                d = json.load(fh)
            d.update(kw)
            with open(p, "w") as fh:
                json.dump(d, fh)
    return bench
