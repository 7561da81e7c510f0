"""ExperimentSpec layer: golden back-compat vs the legacy builders,
executable-cache semantics, sharded execution, flags and validation.

The refactor contract (docs/experiments.md): every legacy builder in
``launch/sim.py`` / ``launch/learn.py`` is a thin deprecated shim over
the spec pipeline — replica pytrees are BITWISE-identical and sweep
results are the same arrays, and each shim warns exactly once per
process.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E
from repro.launch import experiment as X
from repro.launch import learn as LN
from repro.launch import sim as L

# -- helpers ----------------------------------------------------------------


def assert_trees_bitwise_equal(a, b, label=""):
    sa, sb = jax.tree.structure(a), jax.tree.structure(b)
    assert sa == sb, (label, sa, sb)
    for i, (x, y) in enumerate(zip(jax.tree.leaves(a), jax.tree.leaves(b))):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (label, i)


def scenario_spec(n=6, n_tasks=16, n_machines=3, seed=3, **kw):
    return X.ExperimentSpec(
        n, X.FleetAxis(n_machines), X.WorkloadAxis(n_tasks),
        scenario=X.ScenarioAxis((0.0, 0.1), ("nominal", "powersave"),
                                spot_frac=0.5),
        policy=X.PolicyAxis(("mct", "ee_mct")), seed=seed, **kw)


# -- golden back-compat: normalize == legacy make_* -------------------------


def test_normalize_matches_make_replicas_bitwise():
    spec = X.ExperimentSpec(
        6, X.FleetAxis(4), X.WorkloadAxis(24),
        policy=X.PolicyAxis(("fcfs", "met", "mct", "minmin", "ee_mct")),
        seed=5)
    assert_trees_bitwise_equal(X.normalize(spec).legacy(),
                               L.make_replicas(6, 24, 4, seed=5))


def test_normalize_matches_make_scenario_replicas_bitwise():
    spec = X.ExperimentSpec(
        10, X.FleetAxis(3), X.WorkloadAxis(20),
        scenario=X.ScenarioAxis((0.0, 0.1, 0.3), ("nominal", "powersave"),
                                spot_frac=0.5),
        policy=X.PolicyAxis(("mct", "minmin", "ee_mct")), seed=13)
    legacy = L.make_scenario_replicas(
        10, 20, 3, fail_rates=[0.0, 0.1, 0.3],
        dvfs_states=["nominal", "powersave"], seed=13)
    assert_trees_bitwise_equal(X.normalize(spec).legacy(), legacy)


def test_normalize_matches_scenario_replicas_with_arrival_axis():
    spec = X.ExperimentSpec(
        8, X.FleetAxis(3),
        X.WorkloadAxis(16, arrivals=("poisson", "bursty")),
        scenario=X.ScenarioAxis((0.0, 0.1), ("nominal", "powersave"),
                                spot_frac=0.5),
        policy=X.PolicyAxis(("mct",)), seed=0)
    legacy = L.make_scenario_replicas(
        8, 16, 3, policies=["mct"], fail_rates=[0.0, 0.1],
        dvfs_states=["nominal", "powersave"],
        arrivals=("poisson", "bursty"), seed=0)
    assert_trees_bitwise_equal(X.normalize(spec).legacy(), legacy)


def test_normalize_matches_make_workflow_replicas_bitwise():
    spec = X.ExperimentSpec(
        7, X.FleetAxis(3),
        X.WorkloadAxis(14, shapes=("chain", "fork_join", "layered")),
        policy=X.PolicyAxis(("heft", "mct", "rr")), seed=2)
    assert_trees_bitwise_equal(X.normalize(spec).legacy(),
                               L.make_workflow_replicas(7, 14, 3, seed=2))


def test_make_grid_matches_grid_spec_bitwise():
    assert_trees_bitwise_equal(
        X.normalize(LN.grid_spec(6, 16, 3, seed=4)).legacy(),
        LN.make_grid(6, 16, 3, seed=4))


# -- golden back-compat: sweep results --------------------------------------


def test_build_sim_sweep_delegates_to_spec():
    spec = X.ExperimentSpec(5, X.FleetAxis(3), X.WorkloadAxis(16),
                            policy=X.PolicyAxis(("mct", "fcfs")), seed=1)
    res = X.run_experiment(spec)
    legacy_out = L.build_sim_sweep(16, 3)(*res.replicas.legacy())
    assert_trees_bitwise_equal(legacy_out, res.metrics)


def test_build_scenario_sweep_delegates_to_spec():
    spec = scenario_spec()
    res = X.run_experiment(spec)
    legacy_out = L.build_scenario_sweep(16, 3)(*res.replicas.legacy())
    assert_trees_bitwise_equal(legacy_out, res.metrics)


def test_build_traced_sweep_delegates_to_spec():
    spec = X.ExperimentSpec(3, X.FleetAxis(2), X.WorkloadAxis(12),
                            trace=True, seed=7)
    res = X.run_experiment(spec)
    m, tr = L.build_traced_sweep(12, 2)(*res.replicas.legacy())
    assert_trees_bitwise_equal(m, res.metrics)
    assert_trees_bitwise_equal(tr, res.traces)


def test_workflow_sweep_delegates_to_spec():
    spec = X.ExperimentSpec(
        6, X.FleetAxis(3), X.WorkloadAxis(14, shapes=("fork_join",)),
        policy=X.PolicyAxis(("heft", "mct")), seed=2)
    res = X.run_experiment(spec)
    sweep = L.build_scenario_sweep(14, 3, workflow=True)
    legacy_out = sweep(*res.replicas.legacy())
    assert_trees_bitwise_equal(legacy_out, res.metrics)


def test_jitted_scenario_sweep_delegates_to_cache():
    spec = scenario_spec(seed=9)
    reps = X.normalize(spec)
    before = X.cache_stats()["size"]
    sweep = L.jitted_scenario_sweep(16, 3)
    assert X.cache_stats()["size"] == max(before, 1)  # no fresh builder
    out = sweep(reps.tasks, reps.mtype, reps.tables, reps.policy_ids,
                reps.dynamics)
    res = X.run_experiment(spec, replicas=reps)
    assert_trees_bitwise_equal(out, res.metrics)
    assert L.jitted_scenario_sweep(16, 3) is sweep  # stable identity


# -- deprecation: once per builder ------------------------------------------


def test_deprecation_warning_emitted_once_per_builder():
    calls = {
        "build_sim_sweep": lambda: L.build_sim_sweep(8, 2),
        "build_scenario_sweep": lambda: L.build_scenario_sweep(8, 2),
        "build_traced_sweep": lambda: L.build_traced_sweep(8, 2),
        "jitted_scenario_sweep": lambda: L.jitted_scenario_sweep(8, 2),
        "make_scenario_replicas":
            lambda: L.make_scenario_replicas(2, 8, 2, seed=0),
        "make_workflow_replicas":
            lambda: L.make_workflow_replicas(2, 8, 2, seed=0),
        "make_grid": lambda: LN.make_grid(2, 8, 2, seed=0),
    }
    L._WARNED.clear()
    for name, call in calls.items():
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            call()
            dep = [x for x in w if issubclass(x.category,
                                              DeprecationWarning)]
            assert len(dep) == 1, (name, [str(x.message) for x in w])
            assert name in str(dep[0].message)
            assert "ExperimentSpec" in str(dep[0].message)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            call()   # second call: silent
            dep = [x for x in w if issubclass(x.category,
                                              DeprecationWarning)]
            assert not dep, (name, [str(x.message) for x in dep])


def test_make_replicas_is_not_deprecated():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        L.make_replicas(2, 8, 2, seed=0)
        assert not [x for x in w
                    if issubclass(x.category, DeprecationWarning)]


# -- executable cache -------------------------------------------------------


def test_compile_cache_hits_for_same_shape_specs():
    X.clear_cache()
    spec_a = scenario_spec(seed=1)
    spec_b = scenario_spec(seed=2)           # same shapes, new draws
    fa = X.compile_experiment(spec_a)
    fb = X.compile_experiment(spec_b)
    assert fa is fb
    stats = X.cache_stats()
    # retraces counts actual jax traces — none happen at compile time
    # (the callable only traces when first *run* with concrete inputs)
    assert stats == {"hits": 1, "misses": 1, "retraces": 0, "size": 1}
    # a different static engine config is a different executable
    fc = X.compile_experiment(spec_a.with_(sim=E.SimParams(lcap=2)))
    assert fc is not fa
    assert X.cache_stats()["size"] == 2


def test_trace_flag_changes_executable_not_params_identity():
    X.clear_cache()
    spec = X.ExperimentSpec(2, X.FleetAxis(2), X.WorkloadAxis(8))
    f_plain = X.compile_experiment(spec)
    f_trace = X.compile_experiment(spec.with_(trace=True))
    assert f_plain is not f_trace
    assert X.compile_experiment(spec.with_(trace=True)) is f_trace


def test_shared_executable_across_modes():
    """Flat, scenario and workflow specs with the same SimParams share
    ONE cached callable — jax specializes per input structure inside."""
    X.clear_cache()
    flat = X.ExperimentSpec(2, X.FleetAxis(2), X.WorkloadAxis(8))
    scen = scenario_spec(2, 8, 2)
    wf = X.ExperimentSpec(2, X.FleetAxis(2),
                          X.WorkloadAxis(8, shapes=("chain",)),
                          policy=X.PolicyAxis(("heft",)))
    fns = {X.compile_experiment(s) for s in (flat, scen, wf)}
    assert len(fns) == 1
    assert X.cache_stats() == {"hits": 2, "misses": 1, "retraces": 0,
                               "size": 1}
    for s in (flat, scen, wf):               # and they all actually run
        assert X.run_experiment(s).metrics["completed"].shape == (2,)


# -- persistent compilation cache -------------------------------------------

_CACHE_KNOBS = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def cache_config():
    """Snapshot the cache knobs and restore them, so enabling the cache
    here does not make every later compile in this worker write to it."""
    saved = {k: getattr(jax.config, k) for k in _CACHE_KNOBS}
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    yield saved
    for k, v in saved.items():
        jax.config.update(k, v)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)


def test_compilation_cache_honours_env(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert X.enable_compilation_cache() == str(tmp_path)
    assert {k: getattr(jax.config, k) for k in _CACHE_KNOBS} == cache_config
    # another build's executable, with other op_names, is never loaded
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_compilation_cache_default_is_fixed_in_checkout(
        cache_config, monkeypatch, tmp_path):
    import os
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    paths = []
    for cwd in (tmp_path, os.path.dirname(__file__)):
        monkeypatch.chdir(cwd)
        paths.append(X.enable_compilation_cache())
    assert paths[0] == paths[1] == X.DEFAULT_CACHE_DIR
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert paths[0] == os.path.join(root, "results", "jax_cache")
    assert jax.config.jax_compilation_cache_dir == paths[0]


# -- execution: results, flags, sharding ------------------------------------


def test_run_experiment_matches_single_runs():
    spec = scenario_spec(n=4)
    res = X.run_experiment(spec)
    for i in range(4):
        tt, mt, tb, pid, dyn = jax.tree.map(lambda x: x[i],
                                            res.replicas.legacy())
        st = E.run_sim(tt, mt, tb, pid, spec.sim_params, dyn)
        single = X.summarize_replica(st, tb, dyn)
        for k in ("completed", "missed", "cancelled", "preempted"):
            assert int(res.metrics[k][i]) == int(single[k]), (k, i)
        np.testing.assert_allclose(float(res.metrics["energy"][i]),
                                   float(single["energy"]), rtol=1e-4)


def test_run_experiment_sharded_matches_unsharded():
    from repro.launch.mesh import make_local_mesh
    spec = scenario_spec(n=4, seed=11)
    reps = X.normalize(spec)
    plain = X.run_experiment(spec, replicas=reps)
    mesh = make_local_mesh(data=1, model=1)
    sharded = X.run_experiment(spec, replicas=reps, mesh=mesh)
    assert_trees_bitwise_equal(sharded.metrics, plain.metrics)


def test_run_experiment_mesh_divisibility_error():
    from repro.launch.mesh import make_local_mesh, mesh_device_count
    mesh = make_local_mesh(data=1, model=1)
    n_dev = mesh_device_count(mesh)
    spec = X.ExperimentSpec(n_dev + 1, X.FleetAxis(2), X.WorkloadAxis(8))
    if (n_dev + 1) % n_dev == 0:             # single-device edge
        pytest.skip("cannot build an indivisible count on this host")
    with pytest.raises(ValueError, match="must divide"):
        X.run_experiment(spec, mesh=mesh)


def test_learned_flag_with_warm_start_equals_heuristic():
    """An MLP with the MCT warm start takes identical decisions to MCT:
    the learned path through the spec pipeline is exact, not just
    plausible."""
    from repro.core import neural as NN
    from repro.core import schedulers as P
    spec = X.ExperimentSpec(3, X.FleetAxis(3), X.WorkloadAxis(16),
                            policy=X.PolicyAxis(("mct",)), seed=4)
    res_mct = X.run_experiment(spec)
    reps = res_mct.replicas
    mlp_reps = reps._replace(policy_ids=jnp.full_like(
        reps.policy_ids, P.POLICY_IDS["mlp"]))
    res_mlp = X.run_experiment(spec.with_(learned=True),
                               replicas=mlp_reps,
                               policy_params=NN.mct_mlp_params())
    assert_trees_bitwise_equal(res_mlp.metrics, res_mct.metrics)


def test_trace_via_sim_params_returns_traces():
    """trace=True on SimParams directly (not the spec flag) must still
    unpack the (metrics, traces) output correctly."""
    spec = X.ExperimentSpec(2, X.FleetAxis(2), X.WorkloadAxis(8),
                            sim=E.SimParams(trace=True))
    res = X.run_experiment(spec)
    assert res.traces is not None
    assert res.metrics["completed"].shape == (2,)


def test_run_grouped_sweep_rejects_non_flat_replicas():
    reps = X.normalize(scenario_spec(n=2, n_tasks=8, n_machines=2))
    with pytest.raises(ValueError, match="flat replicas"):
        L.run_grouped_sweep(reps)
    flat = X.normalize(X.ExperimentSpec(2, X.FleetAxis(2),
                                        X.WorkloadAxis(8)))
    out = L.run_grouped_sweep(flat)
    assert out["completed"].shape == (2,)


def test_by_policy_rows():
    spec = X.ExperimentSpec(6, X.FleetAxis(3), X.WorkloadAxis(12),
                            policy=X.PolicyAxis(("mct", "fcfs")), seed=0)
    rows = X.run_experiment(spec).by_policy()
    assert [r["policy"] for r in rows] == ["mct", "fcfs"]
    assert all(r["replicas"] == 3 for r in rows)
    assert all(np.isfinite(r["energy"]) for r in rows)


def test_trace_replica_accepts_replicas():
    spec = X.ExperimentSpec(3, X.FleetAxis(2), X.WorkloadAxis(10), seed=6)
    reps = X.normalize(spec)
    st = L.trace_replica(reps, 1)
    assert st.trace is not None
    st2 = L.trace_replica(reps.legacy(), 1)
    assert_trees_bitwise_equal(st.tasks, st2.tasks)


# -- validation -------------------------------------------------------------


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="unknown policies"):
        X.PolicyAxis(("nope",))
    with pytest.raises(ValueError, match="unknown arrival"):
        X.WorkloadAxis(8, arrivals=("nope",))
    with pytest.raises(ValueError, match="unknown workflow"):
        X.WorkloadAxis(8, shapes=("nope",))
    with pytest.raises(ValueError, match="arrivals OR shapes"):
        X.WorkloadAxis(8, arrivals=("poisson",), shapes=("chain",))
    with pytest.raises(ValueError, match="n_replicas"):
        X.ExperimentSpec(0, X.FleetAxis(2), X.WorkloadAxis(8))


def test_registries_are_spec_consumable():
    from repro.core import workload as W
    assert W.resolve_arrivals(("poisson", "bursty")) == ("poisson",
                                                        "bursty")
    assert W.resolve_shapes(("chain",)) == ("chain",)
    with pytest.raises(ValueError, match="already registered"):
        W.register_arrival_generator("poisson", lambda *a: None)
    with pytest.raises(ValueError, match="already registered"):
        W.register_workflow_generator("chain", lambda *a: None)
