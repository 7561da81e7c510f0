"""The availability phase against its per-task gather form.

``engine._availability`` finds each queued task's machine state (down,
kill) by a one-hot compare over the M machines.  The oracle below is the
form it replaced, ``down[m_of]`` / ``dyn.kill[m_of]`` gathered per task;
every leaf of the returned state must be bitwise the oracle's, on random
vmapped states, through ``run_sim`` and through the streaming window.
The structural guard keeps the per-task gather from coming back.
"""
from __future__ import annotations

import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_instance

from repro.core import engine as E
from repro.core import schedulers as P
from repro.core import state as S
from repro.core import streaming as SM
from repro.core import trace as T

INT_MAX = E.INT_MAX


@jax.named_scope("availability")
def availability_gather(st: S.SimState, tb: S.StaticTables,
                        dyn: S.MachineDynamics) -> S.SimState:
    """The availability phase with per-task gathers (the oracle)."""
    tasks, mach = st.tasks, st.machines
    n = tasks.arrival.shape[0]
    n_m = mach.mtype.shape[0]
    down = ~S.machine_up(dyn, st.time)

    running0 = mach.running
    hit = down & (running0 >= 0)
    rid = jnp.clip(running0, 0, n - 1)
    dur = jnp.where(hit, st.time - tasks.t_start[rid], 0.0)
    p_active = tb.power[mach.mtype, 1] * mach.power_scale
    mach = replace(
        mach,
        energy=mach.energy + p_active * dur,
        active_time=mach.active_time + dur,
        running=jnp.where(hit, -1, running0),
    )
    tid_kill = jnp.where(hit & dyn.kill, running0, n)
    tid_req = jnp.where(hit & ~dyn.kill, running0, n)
    if st.trace is not None:
        kinds = jnp.where(dyn.kill, T.EV_PREEMPT, T.EV_REQUEUE)
        st = replace(st, trace=T.record(
            st.trace, st.time, kinds, running0, jnp.arange(n_m), hit))
    status = tasks.status.at[tid_kill].set(S.PREEMPTED, mode="drop") \
                         .at[tid_req].set(S.IN_BATCH, mode="drop")
    t_end = tasks.t_end.at[tid_kill].set(st.time, mode="drop")
    t_start = tasks.t_start.at[tid_req].set(-1.0, mode="drop")
    machine = tasks.machine.at[tid_req].set(-1, mode="drop")
    seq = tasks.seq.at[tid_req].set(INT_MAX, mode="drop")
    n_pre = st.n_preempts.at[jnp.where(hit, running0, n)].add(1, mode="drop")

    m_of = jnp.clip(machine, 0, n_m - 1)
    in_down_q = (status == S.IN_MQ) & (machine >= 0) & down[m_of]
    kq = in_down_q & dyn.kill[m_of]
    rq = in_down_q & ~dyn.kill[m_of]
    if st.trace is not None:
        kinds = jnp.where(dyn.kill[m_of], T.EV_PREEMPT, T.EV_REQUEUE)
        st = replace(st, trace=T.record(
            st.trace, st.time, kinds, jnp.arange(n), machine, in_down_q))
    status = jnp.where(kq, S.PREEMPTED, status)
    t_end = jnp.where(kq, st.time, t_end)
    status = jnp.where(rq, S.IN_BATCH, status)
    machine = jnp.where(rq, -1, machine)
    seq = jnp.where(rq, INT_MAX, seq)
    n_pre = n_pre + in_down_q.astype(jnp.int32)
    mq_count = jnp.where(down, 0, st.mq_count)

    kills = jnp.sum(hit & dyn.kill, dtype=jnp.int32) + \
        jnp.sum(kq, dtype=jnp.int32)
    requeues = jnp.sum(hit & ~dyn.kill, dtype=jnp.int32) + \
        jnp.sum(rq, dtype=jnp.int32)
    tasks = replace(tasks, status=status, t_end=t_end, t_start=t_start,
                    machine=machine, seq=seq)
    return replace(st, tasks=tasks, machines=mach, n_preempts=n_pre,
                   mq_count=mq_count, n_live=st.n_live - kills,
                   n_batch=st.n_batch + requeues)


def assert_bitwise(got, want):
    g_leaves, g_tree = jax.tree_util.tree_flatten(got)
    w_leaves, w_tree = jax.tree_util.tree_flatten(want)
    assert g_tree == w_tree
    for i, (g, w) in enumerate(zip(g_leaves, w_leaves)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, i
        assert g.tobytes() == w.tobytes(), f"leaf {i} differs"


# ---------------------------------------------------------------------------
# random vmapped states
# ---------------------------------------------------------------------------
def random_states(seed: int, r: int, n: int, m: int, down: str,
                  trace: bool):
    """``r`` random states of ``n`` tasks on ``m`` machines, their
    dynamics (``down``: "mixed", "all" or "none" machines down at the
    state's time; kill and requeue mixed) and shared tables."""
    rng = np.random.default_rng(seed)
    n_types, k = 3, 2
    status = rng.choice(np.arange(S.NUM_STATUSES), (r, n),
                        p=[.1, .15, .4, .1, .05, .05, .05, .05, .05])
    # some rows keep machine -1 whatever their status, IN_MQ ones too
    machine = rng.integers(-1, m, (r, n))
    running = np.full((r, m), -1)
    for i in range(r):
        busy = rng.random(m) < 0.7
        running[i, busy] = rng.permutation(n)[:m][busy]
    time = rng.uniform(0.0, 10.0, r).astype(np.float32)
    if down == "mixed":
        start = rng.uniform(0.0, 10.0, (r, m, k))
        end = start + rng.uniform(0.0, 5.0, (r, m, k))
    elif down == "all":
        start = np.zeros((r, m, k))
        end = np.full((r, m, k), np.inf)
    else:
        start = end = np.full((r, m, k), np.inf)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    dyn = S.MachineDynamics(
        speed=f32(rng.uniform(0.5, 1.0, (r, m))),
        power_scale=f32(rng.uniform(0.5, 1.0, (r, m))),
        down_start=f32(start), down_end=f32(end),
        kill=jnp.asarray(rng.random((r, m)) < 0.5))
    tasks = S.TaskTable(
        arrival=f32(rng.uniform(0, 10, (r, n))),
        type_id=i32(rng.integers(0, n_types, (r, n))),
        deadline=f32(rng.uniform(5, 20, (r, n))),
        status=i32(status), machine=i32(machine),
        seq=i32(rng.integers(0, 1000, (r, n))),
        t_start=f32(rng.uniform(-1, 10, (r, n))),
        t_end=f32(rng.uniform(-1, 10, (r, n))))
    mach = S.MachineState(
        mtype=i32(rng.integers(0, 2, (r, m))), running=i32(running),
        busy_until=f32(rng.uniform(0, 20, (r, m))),
        active_time=f32(rng.uniform(0, 5, (r, m))),
        energy=f32(rng.uniform(0, 100, (r, m))),
        speed=dyn.speed, power_scale=dyn.power_scale)
    tb = None
    if trace:
        cap = T.row_capacity_bound(n, 4, m, k)
        tb = jax.vmap(lambda _: T.make_buffer(cap, 8, m, max(n, m)))(
            jnp.arange(r))
        tb = replace(tb, n_rows=i32(rng.integers(0, cap, r)),
                     ev_kind=i32(rng.integers(-1, 9, tb.ev_kind.shape)))
    st = S.SimState(
        time=f32(time), tasks=tasks, machines=mach,
        seq_counter=i32(rng.integers(0, 100, r)),
        rr_ptr=i32(rng.integers(0, m, r)), n_events=i32(np.zeros(r)),
        n_preempts=i32(rng.integers(0, 3, (r, n))),
        mq_count=i32(rng.integers(0, 5, (r, m))), trace=tb,
        n_batch=i32(rng.integers(0, n, r)), n_live=i32(np.full(r, n)))
    rng_t = np.random.default_rng(seed + 1)
    tables = E.make_tables(
        rng_t.uniform(1, 5, (n_types, 2)),
        np.stack([rng_t.uniform(10, 50, 2), rng_t.uniform(60, 200, 2)], 1),
        n)
    return st, dyn, tables


@pytest.mark.parametrize("down", ["mixed", "all", "none"])
@pytest.mark.parametrize("trace", [False, True], ids=["notrace", "trace"])
@pytest.mark.parametrize("m", [1, 16, 64])
def test_availability_matches_gather_form(m, trace, down):
    st, dyn, tables = random_states(1000 + m, r=6, n=96, m=m, down=down,
                                    trace=trace)
    got = jax.jit(jax.vmap(lambda s, d: E._availability(s, tables, d)))(
        st, dyn)
    want = jax.jit(jax.vmap(
        lambda s, d: availability_gather(s, tables, d)))(st, dyn)
    assert_bitwise(got, want)
    if down == "none":
        assert_bitwise(got.tasks, st.tasks)


# ---------------------------------------------------------------------------
# whole runs: the dense loop and the streaming window
# ---------------------------------------------------------------------------
def fleet(m: int, seed: int = 7):
    """A 40-task instance on ``m`` machines with failures that mix kill
    and requeue, three heuristics vmapped."""
    eet, power, wl, mtype = make_instance(seed, n_tasks=40, n_machines=m,
                                          rate=4.0)
    rng = np.random.default_rng(seed)
    start = np.sort(rng.uniform(0, 12, (m, 3)), axis=1)
    end = start + rng.uniform(0.2, 2.0, (m, 3))
    dyn = S.MachineDynamics(
        speed=jnp.full((m,), 0.8, jnp.float32),
        power_scale=jnp.full((m,), 0.7, jnp.float32),
        down_start=jnp.asarray(start, jnp.float32),
        down_end=jnp.asarray(end, jnp.float32),
        kill=jnp.asarray(np.arange(m) % 2 == 0))
    pids = jnp.asarray([P.POLICY_IDS[p] for p in ("mct", "minmin", "rr")])
    return eet, power, wl, jnp.asarray(mtype, jnp.int32), dyn, pids


def with_oracle(monkeypatch, run):
    """``run()`` once with the engine's phase and once with the oracle."""
    got = run()
    monkeypatch.setattr(E, "_availability", availability_gather)
    want = run()
    monkeypatch.undo()
    return got, want


@pytest.mark.parametrize("trace", [False, True], ids=["notrace", "trace"])
def test_run_sim_matches_gather_form(monkeypatch, trace):
    eet, power, wl, mtype, dyn, pids = fleet(5)
    tasks = wl.to_task_table()
    tables = E.make_tables(eet, power, wl.n_tasks)
    params = E.SimParams(lcap=3, trace=trace)

    def run():   # a fresh jit each time: the phase is read at trace time
        return jax.jit(jax.vmap(lambda p: E.run_sim.__wrapped__(
            tasks, mtype, tables, p, params, dyn)))(pids)

    got, want = with_oracle(monkeypatch, run)
    assert int(np.sum(np.asarray(got.n_preempts))) > 0
    assert_bitwise(got, want)


@pytest.mark.parametrize("trace", [False, True], ids=["notrace", "trace"])
def test_stream_window_matches_gather_form(monkeypatch, trace):
    eet, power, wl, mtype, dyn, pids = fleet(4)
    stream = SM.make_stream(wl, 8)
    params = SM.StreamParams(window=16, lcap=3, trace=trace)
    eet_j = jnp.asarray(eet.eet, jnp.float32)
    power_j = jnp.asarray(power, jnp.float32)

    def run():
        return jax.jit(jax.vmap(lambda p: SM.run_stream.__wrapped__(
            stream, mtype, eet_j, power_j, p, params, dyn)))(pids)

    got, want = with_oracle(monkeypatch, run)
    assert int(np.sum(np.asarray(got.sim.n_preempts))) > 0
    assert_bitwise(got, want)


# ---------------------------------------------------------------------------
# structural guard: no per-task gather in the availability scope
# ---------------------------------------------------------------------------
def _loc_names(txt: str):
    """Resolve each ``#locN`` alias of a debug-info module to the set of
    name strings it reaches."""
    defs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", txt, re.M))
    memo: dict[str, set[str]] = {}

    def names(ref: str) -> set[str]:
        if ref not in memo:
            memo[ref] = set()
            body = defs.get(ref, "")
            out = set(re.findall(r'"([^"]*)"', body))
            for sub in re.findall(r"#loc\d+", body):
                out |= names(sub)
            memo[ref] = out
        return memo[ref]

    return names


def per_task_gathers(txt: str, scope: str, n: int) -> list[str]:
    """Gather ops whose location names ``scope`` and whose result is
    ``n`` wide in its last dimension."""
    names = _loc_names(txt)
    hits = []
    for line in txt.splitlines():
        if '"stablehlo.gather"' not in line:
            continue
        shape = re.search(r"-> tensor<([0-9x]+)x[a-z0-9]+>", line)
        loc = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if shape is None or loc is None:
            continue
        last = int(shape.group(1).split("x")[-1])
        if last == n and any(f"/{scope}/" in s for s in names(loc.group(1))):
            hits.append(line.strip())
    return hits


def test_availability_has_no_per_task_gather(monkeypatch):
    n, m = 40, 6
    eet, power, wl, mtype, dyn, pids = fleet(m)
    tasks = wl.to_task_table()
    tables = E.make_tables(eet, power, n)

    def lowered():
        f = jax.vmap(lambda p: E.run_sim.__wrapped__(
            tasks, mtype, tables, p, E.SimParams(lcap=3), dyn))
        return jax.jit(f).lower(pids).as_text(debug_info=True)

    got, want = with_oracle(monkeypatch, lowered)
    # the guard sees the gather form's per-task lookups ...
    assert len(per_task_gathers(want, "availability", n)) >= 2
    # ... and none in the engine's phase
    assert "/availability/" in got
    assert per_task_gathers(got, "availability", n) == []
