"""The benchmark's reference and its input draws are independent of the
program, and agree with it: the draws bitwise with the program's
normalize, the simulation with the program's own oracle
(``core/ref_engine.py``) on small instances, dense and windowed."""
import jax
import numpy as np
import pytest

from bench import check
from bench import harness as H
from bench import inputs as I
from bench import reference as R
from bench.families import independent as F
from repro.core import ref_engine as RE
from repro.core import schedulers as P
from repro.launch import experiment as X

SCEN = {"fail_rates": [0.0, 0.05], "dvfs_states": ["nominal", "powersave"],
        "spot_frac": 0.5, "mttr": 4.0, "n_intervals": 4}
CONFIG = {"n_tasks": 64, "n_machines": 6, "n_task_types": 64,
          "n_machine_types": 6, "rate": 5.0, "scenario": SCEN}
TRAFFIC = {"policies": list(R.HEURISTICS), "arrivals": ["poisson", "bursty"],
           "replicas": 80}
SEED = 2 ** 31 + 977


@pytest.fixture(scope="module")
def program_replicas():
    spec = X.ExperimentSpec(
        n_replicas=TRAFFIC["replicas"], fleet=X.FleetAxis(6, 6),
        workload=X.WorkloadAxis(64, n_task_types=64, rate=5.0,
                                arrivals=("poisson", "bursty")),
        scenario=X.ScenarioAxis(fail_rates=(0.0, 0.05),
                                dvfs_states=("nominal", "powersave"),
                                spot_frac=0.5),
        policy=X.PolicyAxis(tuple(R.HEURISTICS)), seed=SEED)
    return jax.tree.map(np.asarray, X.normalize(spec))


def _program_inputs(reps, r):
    h = jax.tree.map(lambda x: x[r], reps)
    d = h.dynamics
    return {"arrival": h.tasks.arrival, "type_id": h.tasks.type_id,
            "deadline": h.tasks.deadline, "eet": h.tables.eet,
            "power": h.tables.power, "mtype": h.mtype,
            "noise": h.tables.noise, "speed": d.speed,
            "power_scale": d.power_scale, "down_start": d.down_start,
            "down_end": d.down_end, "kill": d.kill}, int(h.policy_ids)


@pytest.mark.parametrize("block", range(4))
def test_draws_equal_the_programs_normalize(program_replicas, block):
    axes = F.axes(CONFIG, TRAFFIC)
    pol = F.replica_policies(axes, TRAFFIC["replicas"])
    for r in range(block * 20, block * 20 + 20):
        mine, policy = I.draw(CONFIG, axes, SEED, r)
        theirs, pid = _program_inputs(program_replicas, r)
        assert P.POLICY_NAMES[pid] == policy == axes["policies"][pol[r]]
        for k, v in mine.items():
            want = np.asarray(theirs[k])
            np.testing.assert_array_equal(np.asarray(v, want.dtype), want,
                                          err_msg=f"replica {r} {k}")


def _oracle(inp, policy, window=None):
    return RE.simulate_ref(
        inp["arrival"], inp["type_id"], inp["deadline"], inp["eet"],
        inp["power"], inp["mtype"], policy=policy, noise=inp["noise"],
        speed=inp["speed"], power_scale=inp["power_scale"],
        down_start=inp["down_start"], down_end=inp["down_end"],
        kill=inp["kill"], window=window)


@pytest.mark.parametrize("policy", R.HEURISTICS)
@pytest.mark.parametrize("window", [None, 8])
def test_reference_matches_the_oracle(policy, window):
    axes = F.axes(CONFIG, dict(TRAFFIC, policies=[policy]))
    for r in (0, 1, 2, 3, 5):
        inp, pol = I.draw(CONFIG, axes, SEED + 3, r)
        mine = R.Replica(inp, pol, window=window).run()
        ref = _oracle(inp, pol, window)
        np.testing.assert_array_equal(mine.status, ref.status)
        np.testing.assert_array_equal(mine.n_preempts, ref.n_preempts)
        np.testing.assert_allclose(mine.t_end, ref.t_end, rtol=1e-12)
        np.testing.assert_allclose(mine.energy, ref.active_energy,
                                   rtol=1e-12)


def test_bfloat16_control_departs_from_the_reference():
    axes = F.axes(CONFIG, TRAFFIC)
    worst = 0.0
    for r in range(0, 40, 3):
        inp, pol = I.draw(CONFIG, axes, SEED, r)
        cg, vg = check.row_gaps(F,
                                R.simulate(inp, pol, precision="bfloat16"),
                                R.simulate(inp, pol, precision="float32"))
        worst = max(worst, vg)
    assert worst > check.LIMITS["value_gap"]


def test_float32_reference_follows_the_program_where_float64_parts():
    """At the stream cell's own size one replica meets a near-tie that
    float32 rounding decides the other way: the float64 reference parts
    from the program there, the float32 one, the configuration's stated
    precision, does not."""
    res = H.resolve("braun512x16.stream")
    cfg, traffic = res["config"], res["traffic"]
    seed, r = 2147507367, 67
    rows = X.run_experiment(H.make_spec(cfg, traffic, seed)).metrics
    prog = {k: np.asarray(rows[k])[r]
            for k in R.COUNT_COLUMNS + R.VALUE_COLUMNS}
    inp, pol = I.draw(cfg, F.axes(cfg, traffic), seed, r)
    window = traffic["streaming"]
    cg32, vg32 = check.row_gaps(F, prog,
                                R.simulate(inp, pol, window=window,
                                           precision="float32"))
    cg64, _ = check.row_gaps(F, prog, R.simulate(inp, pol, window=window))
    assert cg32 <= check.LIMITS["count_gap"]
    assert vg32 <= check.LIMITS["value_gap"]
    assert cg64 > check.LIMITS["count_gap"]
