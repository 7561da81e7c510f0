"""Pegasus scientific workflows (Montage, CyberShake, Epigenomics): the
generators' structure, edge-list precedence with staging delays in the
engine against the float32 oracle for every policy, the zero-delay edge
list against the padded parent table bitwise, and the experiment
pipeline (edge lists, no (N, K) table, draw-span attributes)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E
from repro.core import ref_engine as R
from repro.core import schedulers as P
from repro.core import state as S
from repro.core import telemetry as TL
from repro.core import workload as W
from repro.core.eet import synth_eet
from repro.core.workload import make_scenario
from repro.launch import experiment as X

SHAPES = ("montage", "cybershake", "epigenomics")
POLICIES = list(P.SCHEDULERS)
N_TYPES = 9


def instance(shape: str, seed: int, n: int = 60, bandwidth=5.0):
    """A DAG on 4 machines of 3 types; at 5 MB/s the staging delays are
    a sizable share of a job (Montage's projections: 0.8 time units)."""
    rng = np.random.default_rng(seed)
    prof = W.JOB_PROFILES[shape]
    costs = np.ones(N_TYPES)
    costs[:len(prof.jobs)] = prof.costs(n)
    eet = synth_eet(N_TYPES, 3, inconsistency=0.3, seed=seed, costs=costs)
    power = np.stack([rng.uniform(10, 50, 3), rng.uniform(60, 200, 3)],
                     axis=1).astype(np.float32)
    wf = W.pegasus_workflow(shape, n, N_TYPES, mean_eet=eet.eet.mean(1),
                            bandwidth=bandwidth, seed=seed + 1)
    return eet, power, wf, rng.integers(0, 3, 4)


# --------------------------------------------------------------------------
# Generators
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n", [17, 60, 1000])
@pytest.mark.parametrize("shape", SHAPES)
def test_generator_structure(shape, n):
    wf = W.WORKFLOW_GENERATORS[shape](n, N_TYPES, np.ones(N_TYPES), 3,
                                      bandwidth=100.0)
    prof = W.JOB_PROFILES[shape]
    types = wf.workload.type_id
    assert wf.n_tasks == n
    assert np.all(wf.src < wf.dst) and np.all(np.diff(wf.dst) >= 0)
    np.testing.assert_array_equal(
        np.bincount(types, minlength=len(prof.jobs)), prof.counts(n))
    deg = wf.in_degree
    job = np.asarray(prof.jobs)[types]
    sources = {"montage": "mProjectPP", "cybershake": "ExtractSGT",
               "epigenomics": "fastQSplit"}[shape]
    assert np.all((deg == 0) == (job == sources))
    # each edge carries its parent's output file
    np.testing.assert_array_equal(
        wf.nbytes, np.asarray(prof.output_mb, np.float32)[types[wf.src]])
    if shape == "montage":
        p, d = W.montage_counts(n)
        assert np.all(deg[job == "mDiffFit"] == 2)
        assert deg[job == "mConcatFit"][0] == d
        assert np.all(deg[job == "mBackground"] == 2)
        assert deg[job == "mImgtbl"][0] == p
        # the tail is a chain mAdd -> mShrink -> mJPEG
        assert list(job[-3:]) == ["mAdd", "mShrink", "mJPEG"]
        assert list(wf.src[-3:]) == [n - 4, n - 3, n - 2]
    elif shape == "cybershake":
        s, k = W.cybershake_counts(n)
        assert np.all(deg[job == "SeismogramSynthesis"] == 1)
        assert np.all(deg[job == "PeakValCalcOkaya"] == 1)
        assert list(deg[-2:]) == [k, k]
    else:
        lanes, c, r = W.epigenomics_counts(n)
        assert deg[job == "mapMerge"].sum() == c + (r > 0)
        assert deg[job == "maqIndex"][0] == lanes
    if n == 1000:
        # the configuration's recorded counts
        assert {"montage": 596, "cybershake": 497,
                "epigenomics": 50}[shape] == deg.max()
        assert {"montage": 2389, "cybershake": 1988,
                "epigenomics": 1241}[shape] == wf.n_edges


def test_time_unit_is_the_mean_job():
    for shape, prof in W.JOB_PROFILES.items():
        c = np.asarray(prof.counts(1000), np.float64)
        assert c @ prof.costs(1000) / c.sum() == pytest.approx(1.0)


def test_staging_enters_deadlines_and_ranks():
    _, _, slow, _ = instance("montage", 4, bandwidth=5.0)
    _, _, none, _ = instance("montage", 4, bandwidth=None)
    assert np.all(none.delay == 0) and np.all(slow.delay > 0)
    assert np.all(slow.workload.deadline[slow.in_degree > 0]
                  > none.workload.deadline[slow.in_degree > 0])
    assert np.all(slow.ranks() >= none.ranks())
    assert slow.ranks()[0] > none.ranks()[0]


def test_edge_list_round_trips_the_parent_table():
    _, _, wf, _ = instance("epigenomics", 5)
    back = W.Workflow.from_parents(wf.workload, wf.parents)
    np.testing.assert_array_equal(back.src, wf.src)
    np.testing.assert_array_equal(back.dst, wf.dst)
    e = wf.host_edges(wf.n_edges + 7)
    assert e.src.shape == (wf.n_edges + 7,) and e.end[-1] == wf.n_edges
    assert np.all(e.src[wf.n_edges:] == -1)


# --------------------------------------------------------------------------
# Engine vs oracle, every policy, staging delays and cascades
# --------------------------------------------------------------------------
def _ref(eet, power, wf, mtype, policy, kw):
    wl = wf.workload
    return R.simulate_ref(wl.arrival, wl.type_id, wl.deadline, eet.eet,
                          power, mtype, policy=policy,
                          edges=(wf.src, wf.dst, wf.delay),
                          rank=wf.ranks(eet.eet.mean(1)),
                          precision="float32", **kw)


@pytest.mark.parametrize("dynamic", [False, True])
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("shape", SHAPES)
def test_edge_list_engine_matches_oracle(shape, policy, dynamic):
    eet, power, wf, mtype = instance(shape, 7)
    dyn, kw = None, {}
    if dynamic:
        scen = make_scenario(wf.workload, len(mtype), fail_rate=0.08,
                             mttr=2.0, spot=True, dvfs="powersave", seed=9)
        dyn = scen.dynamics()
        kw = dict(speed=scen.speed, power_scale=scen.power_scale,
                  down_start=scen.down_start, down_end=scen.down_end,
                  kill=scen.kill)
    st = E.simulate(wf, eet, power, mtype, policy=policy, dynamics=dyn)
    ref = _ref(eet, power, wf, mtype, policy, kw)
    np.testing.assert_array_equal(np.asarray(st.tasks.status), ref.status)
    np.testing.assert_array_equal(np.asarray(st.tasks.machine), ref.machine)
    np.testing.assert_array_equal(np.asarray(st.tasks.t_start),
                                  ref.t_start.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(st.tasks.t_end),
                                  ref.t_end.astype(np.float32))
    assert int(st.n_events) == ref.n_events
    # no task started before its inputs were staged
    t_start = np.asarray(st.tasks.t_start)
    t_end = np.asarray(st.tasks.t_end)
    ran = t_start[wf.dst] >= 0
    assert np.all(t_start[wf.dst][ran]
                  >= (t_end[wf.src] + wf.delay)[ran])


@pytest.mark.parametrize("dynamic", [False, True])
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("shape", SHAPES)
def test_zero_delay_edge_list_is_the_parent_table_bitwise(shape, policy,
                                                          dynamic):
    eet, power, wf, mtype = instance(shape, 11, bandwidth=None)
    tables = E.make_tables(eet, power, wf.n_tasks,
                           rank=wf.ranks(eet.eet.mean(1)))
    dyn = None
    if dynamic:
        dyn = make_scenario(wf.workload, len(mtype), fail_rate=0.08,
                            mttr=2.0, spot=False, dvfs="nominal",
                            seed=9).dynamics()
    args = (wf.workload.to_task_table(), jnp.asarray(mtype, jnp.int32),
            tables, P.POLICY_IDS[policy], E.SimParams(), dyn, None)
    a = E.run_sim(*args, parents=jax.device_put(wf.host_edges()))
    b = E.run_sim(*args, parents=jnp.asarray(wf.parents))
    for k in ("status", "machine", "t_start", "t_end"):
        np.testing.assert_array_equal(np.asarray(getattr(a.tasks, k)),
                                      np.asarray(getattr(b.tasks, k)), k)
    np.testing.assert_array_equal(np.asarray(a.machines.energy),
                                  np.asarray(b.machines.energy))


def test_cascade_cancels_the_subtree_of_a_killed_job():
    """CyberShake with an impossible deadline on one extraction: its
    seismograms, their peaks and both zips are cancelled, never run."""
    eet, power, wf, mtype = instance("cybershake", 3, bandwidth=100.0)
    wl = wf.workload
    wl.deadline = wl.deadline.copy()
    wl.deadline[0] = 1e-4
    st = E.simulate(wf, eet, power, mtype, policy="heft")
    status = np.asarray(st.tasks.status)
    kids = wf.dst[wf.src == 0]
    assert status[0] != S.COMPLETED
    assert np.all(status[kids] == S.CANCELLED)
    assert np.all(status[-2:] == S.CANCELLED)
    ref = _ref(eet, power, wf, mtype, "heft", {})
    np.testing.assert_array_equal(status, ref.status)


# --------------------------------------------------------------------------
# The experiment pipeline
# --------------------------------------------------------------------------
def pegasus_spec(n_replicas=30, seed=5, n=40):
    return X.ExperimentSpec(
        n_replicas=n_replicas, fleet=X.FleetAxis(4, 3),
        workload=X.WorkloadAxis(n, N_TYPES, shapes=SHAPES, bandwidth=20.0),
        scenario=X.ScenarioAxis(fail_rates=(0.0, 0.05),
                                dvfs_states=("nominal", "powersave"),
                                spot_frac=0.5),
        policy=X.PolicyAxis(("heft", "minmin", "rr")), seed=seed)


def test_normalize_builds_edge_lists_not_parent_tables():
    spec = pegasus_spec()
    reps = X.normalize(spec)
    e = reps.parents
    assert isinstance(e, S.Edges)
    widest = max(X._generate(spec, c, None).n_edges for c in range(10))
    assert e.src.shape == e.dst.shape == e.delay.shape == (30, widest)
    assert e.end.shape == (30, 40)
    assert e.delay.max() > 0
    # the chunked pre-pass pads every chunk to the same width
    assert X.normalize_chunk(spec, 3, 7).parents.src.shape == (4, widest)


def test_monolithic_and_chunked_agree(tmp_path):
    spec = pegasus_spec()
    mono = X.run_experiment(spec)
    chunked = X.run_experiment(spec, chunk=12, keep_replicas=True)
    for k in ("completed", "cancelled", "missed", "makespan", "energy"):
        np.testing.assert_array_equal(np.asarray(mono.metrics[k]),
                                      np.asarray(chunked.metrics[k]), k)
    assert np.asarray(mono.metrics["cancelled"]).sum() > 0


def test_draw_span_records_edges_and_in_degree(tmp_path):
    TL.enable(str(tmp_path), "pegasus")
    try:
        X.normalize(pegasus_spec(n_replicas=9))
    finally:
        TL.disable()
    (path,) = list(tmp_path.glob("*.jsonl"))
    draws = [s for s in TL.read_jsonl(str(path)) if s.get("name") == "draw"]
    spec = pegasus_spec(n_replicas=9)
    wfs = [X._generate(spec, c, None) for c in range(3)]
    assert draws[0]["n_edges"] == max(w.n_edges for w in wfs)
    assert draws[0]["max_in_degree"] == max(int(w.in_degree.max())
                                            for w in wfs)


def test_bandwidth_needs_shapes_and_job_types():
    with pytest.raises(ValueError, match="bandwidth requires shapes"):
        X.WorkloadAxis(8, bandwidth=10.0)
    with pytest.raises(ValueError, match="job types"):
        X.normalize(X.ExperimentSpec(
            3, X.FleetAxis(2), X.WorkloadAxis(20, 4, shapes=("montage",))))
