"""A later cell is data: a new configuration, traffic file and metric
reader, plus new entries in BENCHMARK.json, load with no file that is
already there edited.  So is a new kind of deployment: a configuration
that names a family of its own brings its spec, its replica layout, its
draws and its reference in ``families/<family>.py``."""
import hashlib
import json
import os

import pytest

from bench import check
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


#: a second family: Braun's tasks in the program's flat mode (no scenario
#: axis), whose replicas are paired by policy, r % n_p
TOY = '''"""Braun's tasks with no scenario: replica r runs policy r % n_p."""
import numpy as np

from bench import inputs as I
from bench import reference
from bench.reference import COUNT_COLUMNS, VALUE_COLUMNS, simulate


def axes(config, traffic):
    return {"policies": list(traffic["policies"]),
            "arrivals": list(traffic["arrivals"])}


def make_spec(config, traffic, seed):
    from repro.launch import experiment as X
    ax = axes(config, traffic)
    return X.ExperimentSpec(
        n_replicas=traffic["replicas"],
        fleet=X.FleetAxis(config["n_machines"], config["n_machine_types"]),
        workload=X.WorkloadAxis(config["n_tasks"],
                                n_task_types=config["n_task_types"],
                                rate=config["rate"],
                                arrivals=tuple(ax["arrivals"])),
        policy=X.PolicyAxis(tuple(ax["policies"])), seed=seed)


def replica_policies(axes, n_replicas):
    return np.arange(n_replicas) % len(axes["policies"])


def draw(config, axes, seed, r):
    n, n_m = config["n_tasks"], config["n_machines"]
    n_tt, n_mt = config["n_task_types"], config["n_machine_types"]
    n_p, n_a = len(axes["policies"]), len(axes["arrivals"])
    rng = np.random.default_rng([seed, r])
    eet = I.synth_eet(n_tt, n_mt, seed + r)
    power = np.stack([rng.uniform(20, 60, n_mt), rng.uniform(80, 300, n_mt)],
                     axis=1).astype(np.float32)
    process = I.ARRIVALS[axes["arrivals"][(r // n_p) % n_a]]
    arrival, type_id, deadline = process(n, config["rate"], n_tt,
                                         eet.mean(1), seed + 7919 * r)
    noise = rng.lognormal(0.0, 0.1, n).astype(np.float32)
    mtype = rng.integers(0, n_mt, n_m)
    never = np.full((n_m, 1), np.inf, np.float32)
    return {"arrival": arrival, "type_id": type_id, "deadline": deadline,
            "eet": eet, "power": power, "mtype": mtype, "noise": noise,
            "speed": np.ones(n_m, np.float32),
            "power_scale": np.ones(n_m, np.float32),
            "down_start": never, "down_end": never.copy(),
            "kill": np.zeros(n_m, bool)}, axes["policies"][r % n_p]
'''
#: the same family with its reference's answer altered
TOY_BUMPED = TOY + '''

def simulate(inputs, policy, window=None, precision="float64"):
    row = reference.simulate(inputs, policy, window=window,
                             precision=precision)
    return dict(row, completed=row["completed"] + 1)
'''
SEED = 2 ** 31 + 70001


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The tiny copy with a config of the toy family and its cell added,
    and the program's two calls of that cell."""
    root = tmp_path_factory.mktemp("toy")
    bench = tiny.make(str(root))
    before = _digests(bench)
    files = {"configs/toy-pairs.json": json.dumps(
                 {"family": "toy", "n_tasks": 30, "n_machines": 3,
                  "n_task_types": 30, "n_machine_types": 3, "rate": 3.0,
                  "precision": "float32", "reduced": []}),
             "traffic/pairs2.json": json.dumps(
                 {"path": "monolithic", "replicas": 8, "devices": 1,
                  "policies": ["mct", "minmin"],
                  "arrivals": ["poisson", "bursty"], "check_per_policy": 2}),
             "families/toy.py": TOY, "families/toy_bumped.py": TOY_BUMPED}
    for name, text in files.items():
        with open(os.path.join(bench, name), "w") as fh:
            fh.write(text)
    b = H.load_benchmark(str(root))
    b["configs"].append({"name": "toy-pairs", "source": "test",
                         "file": "bench/configs/toy-pairs.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "toy-pairs.pairs2", "config": "toy-pairs",
                           "traffic": "pairs2", "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(b, fh)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(H, "BENCH_DIR", bench)
        res = H.resolve("toy-pairs.pairs2")
        cfg, traffic = res["config"], res["traffic"]
        calls = [(s, *res["path"].call(H.make_spec(cfg, traffic, s),
                                       traffic)) for s in (SEED, SEED + 1)]
    return bench, before, res, calls


@pytest.fixture
def in_toy(toy, monkeypatch):
    monkeypatch.setattr(H, "BENCH_DIR", toy[0])
    monkeypatch.setattr(check, "MAX_WORKERS", 1)
    return toy


def test_second_family_loads_without_edits(in_toy):
    bench, before, res, _ = in_toy
    after = _digests(bench)
    assert {k: after[k] for k in before} == before
    assert res["family"].__file__ == os.path.join(bench, "families",
                                                  "toy.py")
    assert H.resolve("braun512x16.grid")["family"].__file__ == os.path.join(
        bench, "families", "independent.py")
    cfg, traffic = res["config"], res["traffic"]
    spec = H.make_spec(cfg, traffic, SEED)
    assert spec == res["family"].make_spec(cfg, traffic, SEED)
    assert spec.scenario is None and spec.n_replicas == 8


def test_second_family_samples_by_its_layout(in_toy, monkeypatch):
    _, _, res, calls = in_toy
    cfg, traffic, fam = res["config"], res["traffic"], res["family"]
    seen = []
    rows = check.reference_rows

    def spy(config, axes, jobs, *a, **k):
        seen.append(list(jobs))
        return rows(config, axes, jobs, *a, **k)
    monkeypatch.setattr(check, "reference_rows", spy)
    numbers = check.compare([c[:3] for c in calls], cfg, traffic, SEED)
    assert check.verdict(numbers), numbers
    axes = fam.axes(cfg, traffic)
    sample = check.draw_sample(fam, axes, 8, 2, 2, SEED)
    assert seen == [[(calls[c][0], r) for c, r in sample]]
    # two of each policy, by the paired layout: replica r runs r % 2
    assert sorted(r % 2 for _, r in sample) == [0, 0, 1, 1]
    assert list(fam.replica_policies(axes, 4)) == [0, 1, 0, 1]


def test_second_familys_reference_decides_correct(in_toy):
    _, _, res, calls = in_toy
    cfg, traffic = res["config"], res["traffic"]
    calls = [c[:3] for c in calls]
    assert check.verdict(check.compare(calls, cfg, traffic, SEED))
    bumped = check.compare(calls, dict(cfg, family="toy_bumped"), traffic,
                           SEED)
    assert bumped["count_gap"] == 1 and not check.verdict(bumped)
