"""Compile the Pallas kernels and a whole pallas sweep for a TPU v5e.

No chip is needed: jax describes a ``v5e:2x2`` topology and the TPU
compiler, installed with jaxlib, compiles for a device that is described
but not attached.  What Mosaic refuses here (unaligned blocks, 1-D
blocks, bool operands, unsupported primitives) it would refuse on the
chip, and interpret-mode tests cannot see it.  Nothing runs, so results
are not checked here (tests/test_kernels*.py do that in interpret mode).

The topology is described inside a module fixture, never at import, so
every pytest worker collects the same tests and only the worker that
runs this file loads the TPU library.  The persistent compilation cache
is switched off around these compiles: an entry compiled for a described
device cannot be read back without one.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine as E
from repro.kernels import sched_argmin as K
from repro.launch import experiment as X

N, M = 2048, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compilation_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _hlo(fn, shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _shapes(one_chip, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]


@pytest.mark.parametrize("rows", [1, N])
def test_masked_argmin_compiles(one_chip, rows):
    """(1, M) is the immediate policies' per-decision machine pick."""
    shapes = _shapes(one_chip, ((rows, M), jnp.float32), ((rows, M), bool))
    hlo = _hlo(lambda v, m: K.masked_argmin(v, m, interpret=False), shapes)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["fused_minmin", "fused_maxmin"])
@pytest.mark.parametrize("n_types", [4, 8])
def test_fused_pair_kernels_compile(one_chip, kernel, n_types):
    fn = getattr(K, kernel)
    shapes = _shapes(one_chip, ((M,), jnp.float32), ((N,), bool),
                     ((M,), bool), ((N,), jnp.int32),
                     ((n_types, M), jnp.float32))
    hlo = _hlo(lambda *a: fn(*a, interpret=False), shapes)
    assert "tpu_custom_call" in hlo


def test_fused_start_pick_compiles(one_chip):
    shapes = _shapes(one_chip, *[((N,), jnp.int32)] * 3)
    hlo = _hlo(lambda s, m, q: K.fused_start_pick(s, m, q, M,
                                                  interpret=False), shapes)
    assert "tpu_custom_call" in hlo


def test_fused_event_bounds_compiles(one_chip):
    shapes = _shapes(one_chip, ((N,), jnp.int32), ((N,), jnp.float32),
                     ((N,), jnp.float32))
    hlo = _hlo(lambda s, a, d: K.fused_event_bounds(s, a, d,
                                                    interpret=False), shapes)
    assert "tpu_custom_call" in hlo


def test_pallas_sweep_compiles(one_chip, monkeypatch):
    """A whole vmapped ``compile_sweep(SimParams(pallas=True))`` with the
    kernels lowered by Mosaic.  ``default_interpret`` reads the default
    backend, which is the CPU here, so the test steers it; a fresh
    executable cache keeps this trace away from every other test."""
    monkeypatch.setattr(K, "default_interpret", lambda: False)
    monkeypatch.setattr(X, "_EXEC_CACHE", {})
    monkeypatch.setattr(X, "_CACHE_STATS", dict(X._CACHE_STATS))
    spec = X.ExperimentSpec(
        n_replicas=4, fleet=X.FleetAxis(8, 2), workload=X.WorkloadAxis(64),
        scenario=X.ScenarioAxis(fail_rates=(0.05,),
                                dvfs_states=("powersave",), spot_frac=0.5),
        policy=X.PolicyAxis(("mct", "minmin", "maxmin", "ee_mct")))
    reps = X.normalize(spec)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip),
        (reps.tasks, reps.mtype, reps.tables, reps.policy_ids,
         reps.dynamics))
    fn = X.compile_sweep(E.SimParams(pallas=True))
    hlo = fn.lower(*args, None, None).compile().as_text()
    assert "tpu_custom_call" in hlo
