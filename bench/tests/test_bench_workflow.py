"""The ``workflow`` family (Pegasus DAGs): its draws are bitwise the
program's normalize, its reference agrees with the program's own
oracle (``core/ref_engine.py`` in float32) and with the program, its
bfloat16 control is not correct, and ``release_us_per_task`` reads the
release phase."""
import jax
import numpy as np
import pytest

from bench import check
from bench import harness as H
from bench import phases as PH
from bench.families import workflow as F
from bench.reference import HEURISTICS
from repro.core import ref_engine as RE
from repro.core import schedulers as P
from repro.launch import experiment as X

CONFIG = dict(H.load_json("configs", "pegasus1000x16"), n_tasks=48,
              n_machines=5, n_machine_types=4)
TRAFFIC = dict(H.load_json("traffic", "dag"), replicas=60, chunk=30)
SEED = 2 ** 31 + 4099
READER = H.load_module("metrics", "release_us_per_task")


@pytest.fixture(scope="module")
def program():
    spec = F.make_spec(CONFIG, TRAFFIC, SEED)
    reps = jax.tree.map(np.asarray, X.normalize(spec))
    res = X.run_experiment(spec, chunk=TRAFFIC["chunk"], keep_replicas=True)
    return reps, res


def test_spec_is_the_cells_grid():
    spec = F.make_spec(CONFIG, TRAFFIC, SEED)
    assert spec.workload.shapes == ("montage", "cybershake", "epigenomics")
    assert spec.workload.bandwidth == CONFIG["bandwidth_mb_s"]
    assert spec.policy.policies == tuple(TRAFFIC["policies"])
    axes = F.axes(CONFIG, TRAFFIC)
    np.testing.assert_array_equal(F.replica_policies(axes, 25),
                                  np.arange(25) % 10)


def test_counts_are_the_configurations():
    full = H.load_json("configs", "pegasus1000x16")
    for shape, counts in full["counts"].items():
        jobs = [j[0] for j in F.JOBS[shape]]
        got = F._counts(shape, full["n_tasks"])
        assert dict(zip(jobs, got.astype(int).tolist())) == {
            k: v for k, v in counts.items() if k in jobs}
        assert [j[1] for j in F.JOBS[shape]] == list(
            full["runtime_s"][shape].values())
        assert [j[2] for j in F.JOBS[shape]] == list(
            full["output_mb"][shape].values())


@pytest.mark.parametrize("block", range(3))
def test_draws_equal_the_programs_normalize(program, block):
    reps, _ = program
    axes = F.axes(CONFIG, TRAFFIC)
    for r in range(block * 20, block * 20 + 20):
        inp, policy = F.draw(CONFIG, axes, SEED, r)
        h = jax.tree.map(lambda x: x[r], reps)
        e, d = h.parents, h.dynamics
        n_e = int(e.end[-1])
        theirs = {"arrival": h.tasks.arrival, "type_id": h.tasks.type_id,
                  "deadline": h.tasks.deadline, "eet": h.tables.eet,
                  "power": h.tables.power, "mtype": h.mtype,
                  "noise": h.tables.noise, "rank": h.tables.rank,
                  "speed": d.speed, "power_scale": d.power_scale,
                  "down_start": d.down_start, "down_end": d.down_end,
                  "kill": d.kill, "src": e.src[:n_e], "dst": e.dst[:n_e],
                  "delay": e.delay[:n_e]}
        assert P.POLICY_NAMES[int(h.policy_ids)] == policy
        assert set(inp) == set(theirs)
        for k, v in inp.items():
            want = np.asarray(theirs[k])
            assert np.asarray(v).dtype.kind == want.dtype.kind, k
            np.testing.assert_array_equal(np.asarray(v, want.dtype), want,
                                          err_msg=f"replica {r} {k}")


def _oracle(inp, policy):
    return RE.simulate_ref(
        inp["arrival"], inp["type_id"], inp["deadline"], inp["eet"],
        inp["power"], inp["mtype"], policy=policy, noise=inp["noise"],
        speed=inp["speed"], power_scale=inp["power_scale"],
        down_start=inp["down_start"], down_end=inp["down_end"],
        kill=inp["kill"], edges=(inp["src"], inp["dst"], inp["delay"]),
        rank=inp["rank"], precision="float32")


@pytest.mark.parametrize("policy", HEURISTICS)
def test_reference_matches_the_oracle(policy):
    axes = F.axes(CONFIG, dict(TRAFFIC, policies=[policy]))
    for r in range(12):             # 3 shapes x 2 fail rates x 2 DVFS
        inp, pol = F.draw(CONFIG, axes, SEED + 5, r)
        mine = F.DagReplica(inp, pol, precision="float32").run()
        ref = _oracle(inp, pol)
        np.testing.assert_array_equal(mine.status, ref.status,
                                      err_msg=f"replica {r}")
        np.testing.assert_array_equal(mine.n_preempts, ref.n_preempts)
        np.testing.assert_array_equal(mine.t_end, ref.t_end)


def test_program_is_correct_and_control_is_not(program):
    _, res = program
    calls = [(SEED, res.metrics, res.agg)]
    numbers = check.compare(calls, CONFIG, TRAFFIC, SEED, workers=1)
    assert check.verdict(numbers), numbers
    control = check.compare([(SEED, {}, None)], CONFIG, TRAFFIC, SEED,
                            control="bfloat16", workers=1)
    assert not check.verdict(control), control


def test_simulate_has_no_window():
    inp, pol = F.draw(CONFIG, F.axes(CONFIG, TRAFFIC), SEED, 0)
    with pytest.raises(ValueError, match="no streaming window"):
        F.simulate(inp, pol, window=8)


def test_release_reader(monkeypatch):
    ops = [["while.1", 0, 100], ["fusion.1", 10, 20], ["fusion.2", 40, 10],
           ["fusion.3", 60, 5]]
    tr = {"devices": {"/device:TPU:0": ops},
          "phases": {"/device:TPU:0": ["other", "release", "drain",
                                       "release"]},
          "annotations": [], "first_call": 0, "phase_source": "tf_op"}
    monkeypatch.setattr(PH, "from_ctx",
                        lambda ctx: dict(tr, window_ns=(0, 200)))
    assert READER.read({"tasks_traced": 5}) == pytest.approx(
        1e6 * 25e-9 / 5)
    # a program without the phase scopes, or no trace: nothing to read
    monkeypatch.setattr(PH, "from_ctx", lambda ctx: dict(
        tr, phases={"/device:TPU:0": ["other"] * 4}, window_ns=(0, 200)))
    assert READER.read({"tasks_traced": 5}) is None
    monkeypatch.setattr(PH, "from_ctx", lambda ctx: None)
    assert READER.read({"tasks_traced": 5}) is None
