"""Parameterized (learned) scheduling policies — paper feature (ii), grown
from "plug in a hand-written rule" to "plug in a trainable policy family".

Two learned policies are registered as ordinary ``schedulers`` entries, so
they dispatch through the same ``lax.switch`` as every heuristic and sweep
/ shard / trace exactly like them:

* ``linear``  score(machine) = w · features(head_task, machine)
* ``mlp``     score(machine) = MLP(features(head_task, machine))
              (one ReLU hidden layer; ReLU keeps the numpy mirror
              bit-reproducible — no transcendental libm differences)

Both are *immediate* policies: they score every machine for the FIFO head
of the batch queue and map it to the machine with the **lowest** score
among those with room (``schedulers._head_decision`` semantics: ties break
to the lowest machine id, down machines are masked out through
``view.room``).

Features (``N_FEATURES`` per (task, machine) pair, built from
``SchedView`` + ``SimState`` — everything the heuristics see, normalized
by the head task's mean EET ``s`` so one parameter vector transfers
across EET scales):

  0  eet / s                expected execution time on this machine
  1  (avail - time) / s     expected wait before the task could start
  2  (completion - time) / s  expected relative completion (MCT's score)
  3  slack / s              deadline - completion (negative: infeasible)
  4  feasible               1.0 if slack >= 0
  5  queue depth / 4        tasks waiting in the machine's local queue
  6  energy / (s * p̄)       expected energy, p̄ = fleet-mean active power
  7  1.0                    bias
  8  ee score               FELARE-style conditional: normalized energy
                            when any machine with room is deadline-
                            feasible (+100 on the infeasible ones), else
                            normalized completion — ``ee_mct``'s exact
                            ranking as a feature, so the learned family
                            contains the best energy-aware heuristic as
                            one weight vector (the training warm start)

``PolicyParams`` carries the weights of BOTH variants in one pytree: the
engine threads a single ``policy_params`` operand through every
``lax.switch`` branch (heuristics ignore it), so the params axis can be
vmapped for population training (``core/train_policy.py``).

``score_machines_np`` is the numpy mirror of the forward pass used by
``core/ref_engine.py`` — float32 throughout, same op order — so the
engine↔oracle parity suite covers learned policies too.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedulers as P
from repro.core import state as S

N_FEATURES = 9
HIDDEN = 16
_EPS = 1e-6
_INFEAS = 100.0     # f8 offset pushing feasible machines ahead (O(1) feats)


class MLPParams(NamedTuple):
    w1: jnp.ndarray    # f32 (N_FEATURES, HIDDEN)
    b1: jnp.ndarray    # f32 (HIDDEN,)
    w2: jnp.ndarray    # f32 (HIDDEN,)
    b2: jnp.ndarray    # f32 ()


class LinearParams(NamedTuple):
    w: jnp.ndarray     # f32 (N_FEATURES,)


class PolicyParams(NamedTuple):
    """One pytree with every learned policy's weights.

    The engine passes a single ``PolicyParams`` to every dispatch, so the
    pytree structure is identical no matter which policy id runs — a
    requirement of ``lax.switch`` and of vmapping the params axis.
    """
    mlp: MLPParams
    linear: LinearParams


def default_params() -> PolicyParams:
    """All-zero weights: every machine scores 0.0, so both learned
    policies degenerate to "first machine with room" (FCFS-machine-order).
    This is the params value the engine substitutes when the caller
    passes none — heuristic-only runs never notice it."""
    return PolicyParams(
        mlp=MLPParams(
            w1=jnp.zeros((N_FEATURES, HIDDEN), jnp.float32),
            b1=jnp.zeros((HIDDEN,), jnp.float32),
            w2=jnp.zeros((HIDDEN,), jnp.float32),
            b2=jnp.zeros((), jnp.float32)),
        linear=LinearParams(w=jnp.zeros((N_FEATURES,), jnp.float32)))


def init_params(seed: int = 0, scale: float = 0.3) -> PolicyParams:
    """Random init for training (small weights: near-uniform scores)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return PolicyParams(
        mlp=MLPParams(
            w1=scale * jax.random.normal(k1, (N_FEATURES, HIDDEN),
                                         jnp.float32) / np.sqrt(N_FEATURES),
            b1=jnp.zeros((HIDDEN,), jnp.float32),
            w2=scale * jax.random.normal(k2, (HIDDEN,), jnp.float32)
            / np.sqrt(HIDDEN),
            b2=jnp.zeros((), jnp.float32)),
        linear=LinearParams(
            w=scale * jax.random.normal(k3, (N_FEATURES,), jnp.float32)))


def mct_mlp_params() -> PolicyParams:
    """Hand-constructed MLP weights that reproduce MCT *exactly*.

    Feature 2 is ``(completion - time)/s`` — a positive monotone
    transform of MCT's score (``s`` is shared by all machines), and it is
    nonnegative, so one identity ReLU unit passes it through unchanged:
    ``score = relu(1.0 * f2)``.  Used as the training warm start, so ES
    explores *around* the best completion-time heuristic instead of from
    noise, and as a parity fixture (mlp(mct_init) must equal mct)."""
    w1 = jnp.zeros((N_FEATURES, HIDDEN), jnp.float32).at[2, 0].set(1.0)
    w2 = jnp.zeros((HIDDEN,), jnp.float32).at[0].set(1.0)
    return PolicyParams(
        mlp=MLPParams(w1=w1, b1=jnp.zeros((HIDDEN,), jnp.float32),
                      w2=w2, b2=jnp.zeros((), jnp.float32)),
        linear=LinearParams(
            w=jnp.zeros((N_FEATURES,), jnp.float32).at[2].set(1.0)))


def ee_mlp_params() -> PolicyParams:
    """Energy-aware warm start: reproduce ``ee_mct`` (FELARE-style).

    Feature 8 *is* ``ee_mct``'s ranking (energy among deadline-feasible
    machines with room, +100 on infeasible ones; pure completion when
    nothing is feasible), and it is nonnegative, so a single identity
    ReLU unit passes it through: ``score = relu(1.0 * f8)``.  ES then
    explores *around* the best energy-aware heuristic; elitist training
    (core/train_policy.py) can only improve on it."""
    w1 = jnp.zeros((N_FEATURES, HIDDEN), jnp.float32).at[8, 0].set(1.0)
    w2 = jnp.zeros((HIDDEN,), jnp.float32).at[0].set(1.0)
    return PolicyParams(
        mlp=MLPParams(w1=w1, b1=jnp.zeros((HIDDEN,), jnp.float32),
                      w2=w2, b2=jnp.zeros((), jnp.float32)),
        linear=LinearParams(
            w=jnp.zeros((N_FEATURES,), jnp.float32).at[8].set(1.0)))


def n_trainable(policy: str) -> int:
    """Flat parameter count of one learned-policy family."""
    p = default_params()
    sub = getattr(p, policy)
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(sub))


# --------------------------------------------------------------------------
# Feature extraction (shared by both learned policies)
# --------------------------------------------------------------------------
def machine_features(state: S.SimState, view: P.SchedView) -> jnp.ndarray:
    """(M, N_FEATURES) features of mapping the head task to each machine.

    Safe when the batch queue is empty (head == -1): features are built
    for task 0 and the caller masks the decision out, exactly like the
    heuristic policies do.
    """
    h = jnp.maximum(view.head, 0)
    eet_row = view.eet_nm[h]                          # (M,)
    en_row = view.energy_nm[h]                        # (M,)
    wait = view.avail - state.time                    # (M,) >= 0
    completion = view.avail + eet_row - state.time    # (M,) >= 0
    slack = state.tasks.deadline[h] - (view.avail + eet_row)
    s = jnp.mean(eet_row) + _EPS                      # scalar, > 0
    pbar = jnp.mean(en_row / (eet_row + _EPS)) + _EPS
    en_n = en_row / (s * pbar)
    comp_n = completion / s
    feas_room = (slack >= 0) & view.room
    ee = jnp.where(feas_room.any(),
                   jnp.where(feas_room, en_n, en_n + _INFEAS), comp_n)
    feats = jnp.stack([
        eet_row / s,
        wait / s,
        comp_n,
        slack / s,
        (slack >= 0).astype(jnp.float32),
        state.mq_count.astype(jnp.float32) / 4.0,
        en_n,
        jnp.ones_like(eet_row),
        ee,
    ], axis=1)
    return feats.astype(jnp.float32)


# f32 matmuls at full precision: a TPU's default passes bf16 operands,
# which would split the engine from its exact-f32 numpy mirror below
_dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def mlp_scores(params: MLPParams, feats: jnp.ndarray) -> jnp.ndarray:
    """(M,) scores; lower = better machine.  ReLU hidden layer."""
    hid = jnp.maximum(_dot(feats, params.w1) + params.b1, 0.0)
    return _dot(hid, params.w2) + params.b2


def linear_scores(params: LinearParams, feats: jnp.ndarray) -> jnp.ndarray:
    return _dot(feats, params.w)


# --------------------------------------------------------------------------
# numpy mirror (used by core/ref_engine.py for parity)
# --------------------------------------------------------------------------
def params_to_numpy(params: PolicyParams | None) -> dict:
    """Host-side float32 copy of the weights for the reference engine."""
    if params is None:
        params = default_params()
    return {
        "w1": np.asarray(params.mlp.w1, np.float32),
        "b1": np.asarray(params.mlp.b1, np.float32),
        "w2": np.asarray(params.mlp.w2, np.float32),
        "b2": np.asarray(params.mlp.b2, np.float32),
        "lw": np.asarray(params.linear.w, np.float32),
    }


def machine_features_np(eet_row, en_row, avail, time, deadline,
                        mq_count, room) -> np.ndarray:
    """numpy mirror of ``machine_features`` (float32, same op order).

    ``room`` is the (M,) bool "queue has space AND machine is up" mask
    (``SchedView.room``) — only the conditional f8 feature reads it."""
    eet_row = np.asarray(eet_row, np.float32)
    en_row = np.asarray(en_row, np.float32)
    avail = np.asarray(avail, np.float32)
    room = np.asarray(room, bool)
    time = np.float32(time)
    deadline = np.float32(deadline)
    wait = avail - time
    completion = avail + eet_row - time
    slack = deadline - (avail + eet_row)
    s = np.float32(np.mean(eet_row) + np.float32(_EPS))
    pbar = np.float32(np.mean(en_row / (eet_row + np.float32(_EPS)))
                      + np.float32(_EPS))
    en_n = en_row / (s * pbar)
    comp_n = completion / s
    feas_room = (slack >= 0) & room
    ee = np.where(feas_room.any(),
                  np.where(feas_room, en_n, en_n + np.float32(_INFEAS)),
                  comp_n)
    return np.stack([
        eet_row / s,
        wait / s,
        comp_n,
        slack / s,
        (slack >= 0).astype(np.float32),
        np.asarray(mq_count, np.float32) / np.float32(4.0),
        en_n,
        np.ones_like(eet_row),
        ee,
    ], axis=1).astype(np.float32)


def score_machines_np(params_np: dict, feats: np.ndarray,
                      kind: str) -> np.ndarray:
    """(M,) scores from the numpy weights; mirrors the jnp forward."""
    feats = np.asarray(feats, np.float32)
    if kind == "linear":
        return feats @ params_np["lw"]
    hid = np.maximum(feats @ params_np["w1"] + params_np["b1"],
                     np.float32(0.0))
    return hid @ params_np["w2"] + params_np["b2"]


# --------------------------------------------------------------------------
# The policies themselves (registered like any user policy)
# --------------------------------------------------------------------------
def mlp_policy(state, tables, view: P.SchedView, rr_ptr,
               params: PolicyParams) -> P.Decision:
    feats = machine_features(state, view)
    scores = mlp_scores(params.mlp, feats)
    scores = jnp.where(view.head >= 0, scores, P.BIG)
    return P._head_decision(view, scores)


def linear_policy(state, tables, view: P.SchedView, rr_ptr,
                  params: PolicyParams) -> P.Decision:
    feats = machine_features(state, view)
    scores = linear_scores(params.linear, feats)
    scores = jnp.where(view.head >= 0, scores, P.BIG)
    return P._head_decision(view, scores)


LEARNED_POLICIES = ("mlp", "linear")

# Registered at import time (repro.core imports this module), so the
# learned policies are ordinary lax.switch branches everywhere: single
# runs, vmapped sweeps, trace capture, the parity suites.
if "mlp" not in P.SCHEDULERS:
    P.register_policy("mlp", mlp_policy)
if "linear" not in P.SCHEDULERS:
    P.register_policy("linear", linear_policy)
