"""What the ``independent`` family reads is pinned: the spec of each
cell's call, the sample a check takes, and the draws and reference rows
of two replicas at the cells' own sizes, so that no change to the
harness moves what the benchmark reads.  Its reference workers import
no jax."""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import check
from bench import harness as H
from repro.launch import experiment as X

POLICIES = ("fcfs", "rr", "met", "mct", "ee_met", "ee_mct", "minmin",
            "maxmin", "edf_mct", "heft")


def _spec(n_replicas, streaming, seed):
    return X.ExperimentSpec(
        n_replicas=n_replicas, fleet=X.FleetAxis(16, 16),
        workload=X.WorkloadAxis(512, n_task_types=512, rate=13.0,
                                arrivals=("poisson", "bursty"),
                                streaming=streaming),
        scenario=X.ScenarioAxis(fail_rates=(0.0, 0.05),
                                dvfs_states=("nominal", "powersave"),
                                spot_frac=0.5, mttr=4.0, n_intervals=4),
        policy=X.PolicyAxis(POLICIES), seed=seed)


#: cell -> (seed, its spec, its sample over three calls, one replica r,
#: sha256 of r's draws, sha256 of r's reference row)
PINS = {
    "braun512x16.grid": (
        2147600053, _spec(2048, None, 2147600053),
        [(2, 80), (2, 242), (0, 965), (1, 1167), (0, 1329), (2, 851),
         (1, 774), (2, 1615), (0, 1899), (1, 1219), (0, 1702), (1, 2022),
         (1, 586), (1, 1067), (0, 551), (0, 1669), (0, 1915), (1, 1832),
         (1, 1838), (2, 356)],
        1234,
        "e20de8c1ec32887a645618be561178c903c848a97ba79661b37869d07f4caeac",
        "a8d5808d0ea4ccc403740549c20d4acf04bee9d7383419232c4d778ee7115b13"),
    "braun512x16.stream": (
        2147700101, _spec(80, 64, 2147700101),
        [(1, 41), (2, 43), (0, 46), (2, 45), (1, 10), (2, 51), (0, 15),
         (1, 15), (1, 18), (2, 59), (1, 62), (2, 63), (1, 24), (1, 65),
         (0, 68), (1, 71), (2, 33), (2, 72), (1, 77), (1, 78)],
        67,
        "af14537e8a06a3ed18facc342ae603ea2492930eeaa9c8d5078ffcbb6004205f",
        "9abd3c0d82a76a37a071a73fb7518bf1fd5402e1607dade25bff9100b2d2ea1a"),
}


def _draw_digest(inp, policy):
    h = hashlib.sha256(policy.encode())
    for k in sorted(inp):
        a = np.ascontiguousarray(inp[k])
        h.update(f"{k}:{a.dtype.str}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _row_digest(row):
    return hashlib.sha256(json.dumps(row, sort_keys=True).encode()
                          ).hexdigest()


@pytest.mark.parametrize("cell", sorted(PINS))
def test_independent_family_reads_what_is_pinned(cell):
    seed, spec, sample, r, draws, row = PINS[cell]
    res = H.resolve(cell)
    cfg, traffic, fam = res["config"], res["traffic"], res["family"]
    assert H.make_spec(cfg, traffic, seed) == spec
    axes = fam.axes(cfg, traffic)
    assert check.draw_sample(fam, axes, traffic["replicas"], 3,
                             traffic["check_per_policy"], seed) == sample
    inp, policy = fam.draw(cfg, axes, seed, r)
    assert _draw_digest(inp, policy) == draws
    got = check.reference_rows(cfg, axes, [(seed, r)],
                               traffic.get("streaming"), cfg["precision"],
                               workers=1)
    assert _row_digest(got[0]) == row


def test_reference_workers_import_no_jax():
    """A worker's whole job, as the spawned pool runs it, in a fresh
    interpreter: the family loaded by name, its draw and reference."""
    code = (
        "import sys\n"
        f"sys.path[:0] = [{H.ROOT!r}]\n"
        "from bench import check, harness as H\n"
        "import bench.families.independent as F\n"
        "cfg = H.load_json('configs', 'braun512x16')\n"
        "traffic = H.load_json('traffic', 'stream')\n"
        "axes = F.axes(cfg, traffic)\n"
        "row = check._ref_row((H.BENCH_DIR, cfg, axes, 2147700101, 67, 64,"
        " 'float32'))\n"
        "F.simulate(*F.draw(cfg, axes, 2147700101, 3), window=64)\n"
        "print(row['completed'], 'jax' in sys.modules,"
        " 'repro' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=H.ROOT,
                         env=dict(os.environ, PYTHONPATH=""))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["231", "False", "False"]
