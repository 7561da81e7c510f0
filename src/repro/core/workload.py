"""Workload + scenario generation and trace loading (E2C "workload"
component, grown into the dynamic-scenario and workflow layers).

E2C's workload component generates task arrivals and lets the user load a
trace CSV.  We support both: synthetic generators (Poisson / uniform /
bursty / diurnal / Markov on-off arrival processes with a task-type
mixture and deadline slack factors) and the E2C trace format
``task_id,task_type,arrival_time[,deadline]``.

A :class:`Scenario` bundles a workload with *machine dynamics* — per-
machine availability traces (fail/repair or spot preemption) and DVFS
operating points — so one object describes everything that varies across
a Monte-Carlo sweep cell (see ``launch/sim.py``).

A :class:`Workflow` adds *precedence constraints*: an edge list sorted by
child, each edge carrying the parent's output file (staged over a shared
storage before the child may run), over a workload whose task ids are a
topological order.  Generators cover the canonical DAG shapes — chains,
fork–join, map–reduce, seeded random layered DAGs — and the Pegasus
scientific workflows (Montage, CyberShake, Epigenomics), and
:func:`upward_ranks` precomputes the HEFT priority used by the ``heft``
scheduling policy (docs/workflows.md).
"""
from __future__ import annotations

import csv
import io
import os
from dataclasses import dataclass

import numpy as np

from repro.core.state import TaskTable


@dataclass
class Workload:
    arrival: np.ndarray    # (N,) f32, sorted ascending
    type_id: np.ndarray    # (N,) i32
    deadline: np.ndarray   # (N,) f32 absolute

    def __post_init__(self):
        self.arrival = np.asarray(self.arrival, np.float32)
        self.type_id = np.asarray(self.type_id, np.int32)
        self.deadline = np.asarray(self.deadline, np.float32)
        order = np.argsort(self.arrival, kind="stable")
        self.arrival = self.arrival[order]
        self.type_id = self.type_id[order]
        self.deadline = self.deadline[order]

    @property
    def n_tasks(self) -> int:
        return self.arrival.shape[0]

    def host_task_table(self) -> TaskTable:
        """The engine's initial task table with numpy leaves: builds it
        on the host and starts no device work."""
        n = self.n_tasks
        return TaskTable(
            arrival=self.arrival,
            type_id=self.type_id,
            deadline=self.deadline,
            status=np.zeros((n,), np.int32),
            machine=np.full((n,), -1, np.int32),
            seq=np.zeros((n,), np.int32),
            t_start=np.zeros((n,), np.float32),
            t_end=np.zeros((n,), np.float32),
        )

    def to_task_table(self) -> TaskTable:
        """:meth:`host_task_table` on the device."""
        import jax
        return jax.device_put(self.host_task_table())


def poisson_workload(n_tasks: int, rate: float, n_task_types: int, *,
                     mean_eet: np.ndarray | None = None,
                     slack: float = 3.0, slack_jitter: float = 0.5,
                     type_probs: np.ndarray | None = None,
                     seed: int = 0) -> Workload:
    """Poisson arrivals at `rate` tasks/sec; deadline = arrival + slack*EETbar.

    ``mean_eet`` is the per-type mean execution time used to scale deadlines
    (if None, 1.0 for every type).  ``slack`` multiplies it; ``slack_jitter``
    adds lognormal jitter so deadlines are not perfectly ordered with
    arrivals (the regime where dropping/cancellation matters).
    """
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n_tasks)
    arrival = np.cumsum(gaps).astype(np.float32)
    if type_probs is None:
        type_probs = np.full(n_task_types, 1.0 / n_task_types)
    type_id = rng.choice(n_task_types, size=n_tasks, p=type_probs)
    if mean_eet is None:
        mean_eet = np.ones(n_task_types, np.float32)
    jitter = rng.lognormal(0.0, slack_jitter, size=n_tasks)
    deadline = arrival + slack * jitter * mean_eet[type_id]
    return Workload(arrival, type_id, deadline.astype(np.float32))


def uniform_workload(n_tasks: int, horizon: float, n_task_types: int, *,
                     mean_eet: np.ndarray | None = None, slack: float = 3.0,
                     seed: int = 0) -> Workload:
    rng = np.random.default_rng(seed)
    arrival = np.sort(rng.uniform(0, horizon, n_tasks)).astype(np.float32)
    type_id = rng.integers(0, n_task_types, n_tasks)
    if mean_eet is None:
        mean_eet = np.ones(n_task_types, np.float32)
    deadline = arrival + slack * mean_eet[type_id]
    return Workload(arrival, type_id, deadline.astype(np.float32))


def bursty_workload(n_tasks: int, rate: float, n_task_types: int, *,
                    burst_factor: float = 8.0, burst_prob: float = 0.1,
                    mean_eet: np.ndarray | None = None, slack: float = 3.0,
                    seed: int = 0) -> Workload:
    """Markov-modulated Poisson: occasional bursts at burst_factor*rate."""
    rng = np.random.default_rng(seed)
    bursting = rng.random(n_tasks) < burst_prob
    rates = np.where(bursting, rate * burst_factor, rate)
    gaps = rng.exponential(1.0 / rates)
    arrival = np.cumsum(gaps).astype(np.float32)
    type_id = rng.integers(0, n_task_types, n_tasks)
    if mean_eet is None:
        mean_eet = np.ones(n_task_types, np.float32)
    deadline = arrival + slack * mean_eet[type_id]
    return Workload(arrival, type_id, deadline.astype(np.float32))


def diurnal_workload(n_tasks: int, base_rate: float, n_task_types: int, *,
                     amplitude: float = 0.8, period: float = 120.0,
                     mean_eet: np.ndarray | None = None, slack: float = 3.0,
                     slack_jitter: float = 0.5, seed: int = 0) -> Workload:
    """Non-homogeneous Poisson with a sinusoidal (diurnal) rate.

    ``rate(t) = base_rate * (1 + amplitude * sin(2*pi*t / period))``,
    sampled exactly by thinning a ``base_rate * (1 + amplitude)``
    homogeneous process.  ``amplitude`` must be in [0, 1] so the rate
    stays nonnegative.  Models the day/night load cycle every serving
    fleet sees — schedulers that look good at constant rate can miss
    deadlines through the daily peak.
    """
    if not 0.0 <= amplitude <= 1.0:
        raise ValueError(f"amplitude must be in [0, 1], got {amplitude}")
    rng = np.random.default_rng(seed)
    rate_max = base_rate * (1.0 + amplitude)
    arrival = np.empty(n_tasks, np.float64)
    t, k = 0.0, 0
    while k < n_tasks:
        t += rng.exponential(1.0 / rate_max)
        rate_t = base_rate * (1.0 + amplitude * np.sin(2 * np.pi * t / period))
        if rng.random() * rate_max <= rate_t:
            arrival[k] = t
            k += 1
    arrival = arrival.astype(np.float32)
    type_id = rng.integers(0, n_task_types, n_tasks)
    if mean_eet is None:
        mean_eet = np.ones(n_task_types, np.float32)
    jitter = rng.lognormal(0.0, slack_jitter, size=n_tasks)
    deadline = arrival + slack * jitter * mean_eet[type_id]
    return Workload(arrival, type_id, deadline.astype(np.float32))


def onoff_workload(n_tasks: int, rate: float, n_task_types: int, *,
                   mean_on: float = 20.0, mean_off: float = 10.0,
                   off_rate_frac: float = 0.05,
                   mean_eet: np.ndarray | None = None, slack: float = 3.0,
                   slack_jitter: float = 0.5, seed: int = 0) -> Workload:
    """Markov-modulated on/off bursts (a true 2-state MMPP).

    A two-state continuous-time Markov chain with exponential dwell
    times: ON emits at ``rate``, OFF at ``off_rate_frac * rate``.  Unlike
    ``bursty_workload`` (iid per-gap rate mixing) the burst *lengths* are
    correlated, so machine queues saturate and drain in waves.
    """
    rng = np.random.default_rng(seed)
    arrival = np.empty(n_tasks, np.float64)
    t, k = 0.0, 0
    on = True
    t_switch = rng.exponential(mean_on)
    while k < n_tasks:
        r = rate if on else max(rate * off_rate_frac, 1e-9)
        gap = rng.exponential(1.0 / r)
        if t + gap >= t_switch:
            # memoryless: restart the draw from the switch point
            t = t_switch
            on = not on
            t_switch = t + rng.exponential(mean_on if on else mean_off)
            continue
        t += gap
        arrival[k] = t
        k += 1
    arrival = arrival.astype(np.float32)
    type_id = rng.integers(0, n_task_types, n_tasks)
    if mean_eet is None:
        mean_eet = np.ones(n_task_types, np.float32)
    jitter = rng.lognormal(0.0, slack_jitter, size=n_tasks)
    deadline = arrival + slack * jitter * mean_eet[type_id]
    return Workload(arrival, type_id, deadline.astype(np.float32))


# Named arrival processes with a common call shape, so experiment specs
# can treat "arrival pattern" as a sweep axis (launch/experiment.py):
# f(n_tasks, rate, n_task_types, mean_eet, seed) -> Workload
ARRIVAL_GENERATORS = {
    "poisson": lambda n, rate, ntt, me, seed: poisson_workload(
        n, rate=rate, n_task_types=ntt, mean_eet=me, slack=4.0, seed=seed),
    "bursty": lambda n, rate, ntt, me, seed: bursty_workload(
        n, rate=rate, n_task_types=ntt, mean_eet=me, slack=4.0, seed=seed),
    "diurnal": lambda n, rate, ntt, me, seed: diurnal_workload(
        n, base_rate=rate, n_task_types=ntt, mean_eet=me, slack=4.0,
        seed=seed),
    "onoff": lambda n, rate, ntt, me, seed: onoff_workload(
        n, rate=rate, n_task_types=ntt, mean_eet=me, slack=4.0, seed=seed),
}


def iter_workload_chunks(w: Workload, chunk: int):
    """Yield ``w`` as consecutive ``Workload`` slices of ``chunk`` tasks
    (tail may be short) — the host-side view of the arrival stream the
    streaming engine consumes (``streaming.make_stream`` packs the same
    slices into device columns).  Order is arrival order, so
    concatenating the chunks reproduces ``w`` exactly."""
    chunk = int(chunk)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    for i in range(0, w.n_tasks, chunk):
        yield Workload(w.arrival[i:i + chunk], w.type_id[i:i + chunk],
                       w.deadline[i:i + chunk])


def poisson_workload_chunks(n_tasks: int, chunk: int, rate: float,
                            n_task_types: int, *,
                            mean_eet: np.ndarray | None = None,
                            slack: float = 3.0, slack_jitter: float = 0.5,
                            type_probs: np.ndarray | None = None,
                            seed: int = 0):
    """Generate a Poisson workload chunk-by-chunk in O(chunk) memory —
    the streaming-native arrival source for unbounded N.

    Each chunk draws from an independent substream
    (``default_rng([seed, chunk_index])``) with arrivals continuing from
    the previous chunk's last arrival, so any prefix of the stream is
    reproducible without generating what came before.  The process is
    statistically identical to :func:`poisson_workload` but NOT
    bitwise-equal to it (different draw order); streaming parity tests
    use a dense workload split by :func:`iter_workload_chunks` instead.
    """
    chunk = int(chunk)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if type_probs is None:
        type_probs = np.full(n_task_types, 1.0 / n_task_types)
    if mean_eet is None:
        mean_eet = np.ones(n_task_types, np.float32)
    t0 = 0.0
    for ci, lo in enumerate(range(0, n_tasks, chunk)):
        m = min(chunk, n_tasks - lo)
        rng = np.random.default_rng([seed, ci])
        gaps = rng.exponential(1.0 / rate, size=m)
        arrival = (t0 + np.cumsum(gaps)).astype(np.float32)
        t0 = float(arrival[-1])
        type_id = rng.choice(n_task_types, size=m, p=type_probs)
        jitter = rng.lognormal(0.0, slack_jitter, size=m)
        deadline = arrival + slack * jitter * np.asarray(mean_eet)[type_id]
        yield Workload(arrival, type_id, deadline.astype(np.float32))


def register_arrival_generator(name: str, fn) -> None:
    """Register a custom arrival process as a sweep axis value.

    ``fn(n_tasks, rate, n_task_types, mean_eet, seed) -> Workload``.
    Registered names are immediately valid in
    ``experiment.WorkloadAxis(arrivals=...)``; duplicates raise."""
    if name in ARRIVAL_GENERATORS:
        raise ValueError(f"arrival generator {name!r} already registered")
    ARRIVAL_GENERATORS[name] = fn


def resolve_arrivals(names) -> tuple[str, ...]:
    """Validate arrival-generator names against the registry (the
    spec-consumable view of ``ARRIVAL_GENERATORS``)."""
    names = tuple(names)
    unknown = [n for n in names if n not in ARRIVAL_GENERATORS]
    if unknown:
        raise ValueError(f"unknown arrival generators {unknown}; known: "
                         f"{sorted(ARRIVAL_GENERATORS)}")
    return names


# ---------------------------------------------------------------------------
# Workflows: precedence-constrained (DAG) workloads
# ---------------------------------------------------------------------------
@dataclass
class Workflow:
    """A precedence-constrained workload: tasks + a DAG as an edge list.

    Edge ``e`` runs from ``src[e]`` to ``dst[e]``: task ``dst[e]`` may
    enter the system only once ``src[e]`` *completed* and its output
    file (``nbytes[e]`` MB) has been staged over the shared storage
    (``bandwidth`` MB per simulated time unit; None = no staging cost).
    A task's effective arrival is ``max(arrival[i], max over in-edges of
    t_end[src] + delay)``.  Edges are sorted by ``dst`` (CSR order, the
    engine's ``state.Edges``), and task ids must be a topological order
    (``src[e] < dst[e]``) — every generator below guarantees it, and it
    is what lets :func:`upward_ranks` run in one reverse sweep.

    IMPORTANT: ``Workload`` sorts tasks by arrival time on construction.
    Edge ids index the *sorted* order, so a workflow's arrival times
    must be nondecreasing in task id (the generators emit a common
    submission time ``t0``, which trivially satisfies this).
    """

    workload: Workload
    src: np.ndarray                 # (E,) i32 parent
    dst: np.ndarray                 # (E,) i32 child, nondecreasing
    nbytes: np.ndarray | None = None    # (E,) f32 MB staged; None = 0
    bandwidth: float | None = None  # MB per simulated time unit

    def __post_init__(self):
        self.src = np.asarray(self.src, np.int32).reshape(-1)
        self.dst = np.asarray(self.dst, np.int32).reshape(-1)
        self.nbytes = np.zeros(self.src.shape, np.float32) \
            if self.nbytes is None else np.asarray(self.nbytes, np.float32)
        n = self.workload.n_tasks
        if not (self.src.shape == self.dst.shape == self.nbytes.shape):
            raise ValueError("src, dst and nbytes must be (E,) each")
        if np.any(self.src < 0) or np.any(self.dst >= n) \
                or np.any(self.src >= self.dst):
            raise ValueError("edges must satisfy 0 <= src[e] < dst[e] < "
                             "n_tasks (task ids are a topological order)")
        if np.any(np.diff(self.dst) < 0):
            raise ValueError("edges must be sorted by dst")
        if np.any(np.diff(self.workload.arrival) < 0):
            raise ValueError("workflow arrivals must be nondecreasing in "
                             "task id (ids index the sorted workload)")

    @classmethod
    def from_parents(cls, workload: Workload, parents, nbytes=None,
                     bandwidth: float | None = None) -> "Workflow":
        """From a padded (N, K) parent table (``-1`` = no parent);
        ``nbytes`` (optional) is aligned with it."""
        parents = np.asarray(parents, np.int32)
        if parents.ndim != 2 or parents.shape[0] != workload.n_tasks:
            raise ValueError(
                f"parents must be (n_tasks, K), got {parents.shape}")
        dst, slot = np.nonzero(parents >= 0)
        if np.any(parents[dst, slot] >= dst):
            raise ValueError("parents must satisfy -1 <= parents[i, k] < i "
                             "(task ids are a topological order)")
        return cls(workload, parents[dst, slot], dst,
                   None if nbytes is None
                   else np.asarray(nbytes, np.float32)[dst, slot],
                   bandwidth)

    @property
    def n_tasks(self) -> int:
        return self.workload.n_tasks

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])

    @property
    def in_degree(self) -> np.ndarray:
        return np.bincount(self.dst, minlength=self.n_tasks)

    @property
    def delay(self) -> np.ndarray:
        """(E,) f32 staging delay of each edge, in simulated time units."""
        if self.bandwidth is None:
            return np.zeros(self.src.shape, np.float32)
        return (self.nbytes / np.float32(self.bandwidth)).astype(np.float32)

    @property
    def parents(self) -> np.ndarray:
        """The padded (N, K) parent table (``-1`` pad), derived from the
        edge list — the form the streaming window and the Gantt view
        read."""
        k = max(int(self.in_degree.max(initial=0)), 1)
        out = np.full((self.n_tasks, k), -1, np.int32)
        first = np.searchsorted(self.dst, self.dst, side="left")
        out[self.dst, np.arange(self.n_edges) - first] = self.src
        return out

    def host_edges(self, width: int | None = None):
        """The engine's ``state.Edges`` with numpy leaves, padded to
        ``width`` edges (default: none) with edges in no task's range."""
        from repro.core.state import Edges, pad_edges
        first = np.searchsorted(self.dst, self.dst, side="left")
        e = Edges(src=self.src, dst=self.dst, delay=self.delay,
                  pos=(np.arange(self.n_edges) - first).astype(np.int32),
                  end=np.searchsorted(self.dst, np.arange(self.n_tasks),
                                      side="right").astype(np.int32))
        return e if width is None else pad_edges(e, int(width))

    def ranks(self, mean_eet: np.ndarray | None = None) -> np.ndarray:
        """(N,) HEFT upward ranks, staging delays included;
        ``mean_eet`` is the per-*type* mean execution time across
        machine types (``eet.eet.mean(axis=1)``)."""
        if mean_eet is None:
            w = np.ones(self.n_tasks, np.float64)
        else:
            w = np.asarray(mean_eet, np.float64)[self.workload.type_id]
        return upward_ranks(self.src, self.dst, w, self.delay)


def upward_ranks(src, dst, w, delay=None) -> np.ndarray:
    """HEFT upward rank: ``rank(i) = w[i] + max over child edges
    (delay[e] + rank(c))`` (Topcuoglu et al. 2002, with the mean
    communication cost c̄ of an edge its staging delay, which no machine
    choice changes), i.e. the expected length of the longest path from a
    task to a DAG exit.  ``w`` is the per-task mean expected execution
    time.  One reverse sweep over the dst-sorted edges: when an edge is
    visited its child's rank is final, so it relaxes the parent.
    """
    src, dst = np.asarray(src), np.asarray(dst)
    w = np.asarray(w, np.float64)
    rank = w.copy()
    d = np.zeros(src.shape, np.float64) if delay is None \
        else np.asarray(delay, np.float64)
    for e in range(src.shape[0] - 1, -1, -1):
        p = src[e]
        rank[p] = max(rank[p], w[p] + (d[e] + rank[dst[e]]))
    return rank.astype(np.float32)


def _edges_of(parent_lists: list[list[int]]) -> tuple[np.ndarray,
                                                      np.ndarray]:
    """(src, dst) of per-task parent lists, dst-sorted, src-sorted
    within a task."""
    src = [p for ps in parent_lists for p in sorted(ps)]
    dst = [i for i, ps in enumerate(parent_lists) for _ in ps]
    return np.asarray(src, np.int32), np.asarray(dst, np.int32)


def _assemble_workflow(parent_lists: list[list[int]], n_task_types: int,
                       mean_eet: np.ndarray | None, t0: float,
                       slack: float, slack_jitter: float,
                       rng: np.random.Generator, *,
                       type_id: np.ndarray | None = None,
                       nbytes: np.ndarray | None = None,
                       bandwidth: float | None = None) -> Workflow:
    """Common generator tail: types, path-aware deadlines, edge list.

    Deadlines scale with each task's expected *critical-path length from
    the sources* (``cum``, staging delays included), not its own EET
    alone — a slack factor that ignored the chain depth would doom every
    deep task.  ``type_id`` (optional) fixes the task types; otherwise
    they are drawn uniformly.
    """
    n = len(parent_lists)
    if type_id is None:
        type_id = rng.integers(0, n_task_types, n)
    me = np.ones(n_task_types, np.float32) if mean_eet is None \
        else np.asarray(mean_eet, np.float32)
    w = me[type_id].astype(np.float64)
    src, dst = _edges_of(parent_lists)
    wl = Workload(np.full(n, t0, np.float32), type_id,
                  np.zeros(n, np.float32))
    wf = Workflow(wl, src, dst, nbytes, bandwidth)
    delay = wf.delay.astype(np.float64)
    cum = w.copy()
    best = np.full(n, -np.inf)
    for e in range(src.shape[0]):
        i = dst[e]
        best[i] = max(best[i], cum[src[e]] + delay[e])
        if e + 1 == src.shape[0] or dst[e + 1] != i:
            cum[i] = w[i] + best[i]
    jitter = rng.lognormal(0.0, slack_jitter, n) if slack_jitter > 0 \
        else np.ones(n)
    wl.deadline = (t0 + slack * jitter * cum).astype(np.float32)
    return wf


def chain_workflow(n_tasks: int, n_task_types: int = 1, *,
                   mean_eet: np.ndarray | None = None, t0: float = 0.0,
                   slack: float = 4.0, slack_jitter: float = 0.0,
                   seed: int = 0) -> Workflow:
    """A single chain ``0 -> 1 -> ... -> n-1`` (fully sequential)."""
    rng = np.random.default_rng(seed)
    parent_lists = [[] if i == 0 else [i - 1] for i in range(n_tasks)]
    return _assemble_workflow(parent_lists, n_task_types, mean_eet, t0,
                              slack, slack_jitter, rng)

def fork_join_workflow(n_branches: int, branch_len: int = 1,
                       n_task_types: int = 1, *,
                       mean_eet: np.ndarray | None = None, t0: float = 0.0,
                       slack: float = 4.0, slack_jitter: float = 0.0,
                       seed: int = 0) -> Workflow:
    """Source -> ``n_branches`` parallel chains of ``branch_len`` -> join.

    The canonical scatter/gather shape (N = n_branches*branch_len + 2):
    heterogeneity-aware placement of the branches is exactly where HEFT
    beats load-blind policies.
    """
    rng = np.random.default_rng(seed)
    parent_lists: list[list[int]] = [[]]                       # source = 0
    for b in range(n_branches):
        for j in range(branch_len):
            first = b * branch_len + 1
            parent_lists.append([0] if j == 0 else [first + j - 1])
    parent_lists.append([1 + b * branch_len + branch_len - 1
                         for b in range(n_branches)])          # join
    return _assemble_workflow(parent_lists, n_task_types, mean_eet, t0,
                              slack, slack_jitter, rng)


def map_reduce_workflow(n_maps: int, n_reduces: int = 1,
                        n_task_types: int = 1, *,
                        mean_eet: np.ndarray | None = None, t0: float = 0.0,
                        slack: float = 4.0, slack_jitter: float = 0.0,
                        seed: int = 0) -> Workflow:
    """``n_maps`` independent maps, then ``n_reduces`` reduces that each
    depend on *every* map (a full shuffle barrier; in-degree = n_maps)."""
    rng = np.random.default_rng(seed)
    maps = list(range(n_maps))
    parent_lists = [[] for _ in maps] + [list(maps)
                                         for _ in range(n_reduces)]
    return _assemble_workflow(parent_lists, n_task_types, mean_eet, t0,
                              slack, slack_jitter, rng)


def layered_workflow(n_tasks: int, n_task_types: int = 1, *,
                     n_layers: int = 4, max_parents: int = 3,
                     mean_eet: np.ndarray | None = None, t0: float = 0.0,
                     slack: float = 4.0, slack_jitter: float = 0.0,
                     seed: int = 0) -> Workflow:
    """Seeded random layered DAG: tasks are split into ``n_layers``
    contiguous layers; each task after the first layer draws 1 to
    ``max_parents`` distinct parents uniformly from the previous layer.
    The property-test shape (tests/test_workflows.py): random but
    reproducible, with bounded in-degree.
    """
    if n_layers < 1:
        raise ValueError("n_layers must be >= 1")
    rng = np.random.default_rng(seed)
    bounds = np.linspace(0, n_tasks, n_layers + 1).astype(int)
    parent_lists: list[list[int]] = []
    for layer in range(n_layers):
        lo, hi = bounds[layer], bounds[layer + 1]
        prev = list(range(bounds[layer - 1], lo)) if layer else []
        for _ in range(lo, hi):
            if not prev:
                parent_lists.append([])
            else:
                k = int(rng.integers(1, min(max_parents, len(prev)) + 1))
                parent_lists.append(sorted(
                    rng.choice(len(prev), size=k, replace=False)))
                parent_lists[-1] = [prev[j] for j in parent_lists[-1]]
    return _assemble_workflow(parent_lists, n_task_types, mean_eet, t0,
                              slack, slack_jitter, rng)


# ---------------------------------------------------------------------------
# Pegasus scientific workflows (Bharathi et al., "Characterization of
# Scientific Workflows", WORKS 2008; Juve et al., "Characterizing and
# profiling scientific workflows", FGCS 29(3), 2013)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class JobProfile:
    """The job types of a Pegasus workflow: a task's type is its job type.

    ``runtime_s`` is each job type's mean runtime (seconds) and
    ``output_mb`` the size of the file a job of the type hands each of
    its children.  One simulated time unit is the workflow's mean job
    runtime at its task counts (``counts(n)``), so a fail rate or an MTTR
    means for a workflow what it means for Braun's tasks.  The numbers
    are ``bench/configs/pegasus1000x16.json``'s ``assumed`` ones.
    """
    jobs: tuple[str, ...]
    runtime_s: tuple[float, ...]
    output_mb: tuple[float, ...]
    counts: object          # n_tasks -> (J,) tasks of each job type

    def seconds_per_unit(self, n_tasks: int) -> float:
        c = np.asarray(self.counts(n_tasks), np.float64)
        return float(c @ np.asarray(self.runtime_s, np.float64) / c.sum())

    def costs(self, n_tasks: int) -> np.ndarray:
        """(J,) mean runtime of each job type in simulated time units."""
        return np.asarray(self.runtime_s, np.float64) / \
            self.seconds_per_unit(n_tasks)


def montage_counts(n: int) -> tuple[int, int]:
    """(P mProjectPP, D mDiffFit) with 2P + D + 6 = n and D ≈ 3P
    (overlapping pairs; at least one, at most P(P-1)/2)."""
    p = max(2, round((n - 6) / 5))
    while n - 6 - 2 * p > p * (p - 1) // 2:
        p += 1
    if n - 6 - 2 * p < 1:
        raise ValueError(f"montage needs at least 11 tasks, got {n}")
    return p, n - 6 - 2 * p


def cybershake_counts(n: int) -> tuple[int, int]:
    """(S ExtractSGT, K SeismogramSynthesis) with S + 2K + 2 = n."""
    s = max(2, round(n / 250))
    s += (n - 2 - s) % 2
    if n - 2 - s < 2:
        raise ValueError(f"cybershake needs at least 6 tasks, got {n}")
    return s, (n - 2 - s) // 2


def epigenomics_counts(n: int) -> tuple[int, int, int]:
    """(L lanes, C whole 4-stage chunks, r stages of a last partial
    chunk) with 2L + 4C + r + 2 = n; L odd, about n / 250."""
    lanes = max(1, n // 250) | 1
    if n - 2 - 2 * lanes < 4:
        raise ValueError(f"epigenomics needs at least 8 tasks, got {n}")
    c, r = divmod(n - 2 - 2 * lanes, 4)
    return lanes, c, r


def _montage_type_counts(n):
    p, d = montage_counts(n)
    return [p, d, 1, 1, p, 1, 1, 1, 1]


def _cybershake_type_counts(n):
    s, k = cybershake_counts(n)
    return [s, k, k, 1, 1]


def _epigenomics_type_counts(n):
    lanes, c, r = epigenomics_counts(n)
    return [lanes] + [c + (r > j) for j in range(4)] + [lanes, 1, 1]


JOB_PROFILES: dict[str, JobProfile] = {
    "montage": JobProfile(
        ("mProjectPP", "mDiffFit", "mConcatFit", "mBgModel", "mBackground",
         "mImgtbl", "mAdd", "mShrink", "mJPEG"),
        (1.73, 0.66, 143.26, 384.49, 1.72, 2.78, 282.37, 66.10, 0.64),
        (8.0, 0.01, 0.1, 0.1, 8.0, 0.01, 170.0, 1.0, 0.3),
        _montage_type_counts),
    "cybershake": JobProfile(
        ("ExtractSGT", "SeismogramSynthesis", "PeakValCalcOkaya",
         "ZipSeis", "ZipPSA"),
        (110.58, 79.47, 0.55, 265.73, 195.80),
        (150.0, 0.02, 0.001, 10.0, 0.5),
        _cybershake_type_counts),
    "epigenomics": JobProfile(
        ("fastQSplit", "filterContams", "sol2sanger", "fastq2bfq", "map",
         "mapMerge", "maqIndex", "pileup"),
        (34.34, 2.47, 0.48, 1.40, 201.78, 11.00, 43.57, 55.95),
        (10.0, 10.0, 10.0, 3.0, 1.5, 30.0, 100.0, 80.0),
        _epigenomics_type_counts),
}


def _montage_dag(n: int, rng) -> tuple[list[int], list[list[int]]]:
    """mProjectPP x P -> mDiffFit x D (two overlapping projections
    each) -> mConcatFit -> mBgModel -> mBackground x P (mBgModel and
    its own projection) -> mImgtbl -> mAdd -> mShrink -> mJPEG.  The D
    pairs are drawn from the images' nearest neighbours in id order."""
    p, d = montage_counts(n)
    cand = []
    k = 1
    while len(cand) < d:
        cand += [(i, i + k) for i in range(p - k)]
        k += 1
    pairs = [cand[j] for j in np.sort(rng.choice(len(cand), d,
                                                 replace=False))]
    diff0, concat = p, p + d
    bg0 = p + d + 2
    imgtbl = bg0 + p
    types = [0] * p + [1] * d + [2, 3] + [4] * p + [5, 6, 7, 8]
    parents = [[] for _ in range(p)] + [list(ab) for ab in pairs]
    parents += [list(range(diff0, diff0 + d)), [concat]]
    parents += [[i, concat + 1] for i in range(p)]
    parents += [list(range(bg0, bg0 + p)), [imgtbl], [imgtbl + 1],
                [imgtbl + 2]]
    return types, parents


def _cybershake_dag(n: int, rng) -> tuple[list[int], list[list[int]]]:
    """ExtractSGT x S -> SeismogramSynthesis x K (one extraction each,
    drawn) -> PeakValCalcOkaya x K (its own seismogram); ZipSeis over
    every seismogram, ZipPSA over every peak value."""
    s, k = cybershake_counts(n)
    sgt = np.sort(rng.integers(0, s, k))
    types = [0] * s + [1] * k + [2] * k + [3, 4]
    parents = [[] for _ in range(s)] + [[int(g)] for g in sgt]
    parents += [[s + j] for j in range(k)]
    parents += [list(range(s, s + k)), list(range(s + k, s + 2 * k))]
    return types, parents


def _epigenomics_dag(n: int, rng) -> tuple[list[int], list[list[int]]]:
    """fastQSplit x L -> per chunk the chain filterContams -> sol2sanger
    -> fastq2bfq -> map -> mapMerge of its lane; maqIndex over every
    mapMerge, then pileup.  Chunks go to lanes round-robin; a last
    partial chunk (r < 4 stages, only where n - 2 - 2L is not a
    multiple of 4) feeds its lane's mapMerge from its last stage."""
    lanes, c, r = epigenomics_counts(n)
    types = [0] * lanes
    parents: list[list[int]] = [[] for _ in range(lanes)]
    merge_in: list[list[int]] = [[] for _ in range(lanes)]
    for j, stages in enumerate([4] * c + ([r] if r else [])):
        lane = j % lanes
        prev = lane
        for st in range(stages):
            types.append(1 + st)
            parents.append([prev])
            prev = len(parents) - 1
        merge_in[lane].append(prev)
    merge0 = len(parents)
    types += [5] * lanes + [6, 7]
    parents += merge_in + [list(range(merge0, merge0 + lanes)),
                           [merge0 + lanes]]
    return types, parents


_PEGASUS_DAGS = {"montage": _montage_dag, "cybershake": _cybershake_dag,
                 "epigenomics": _epigenomics_dag}


def pegasus_workflow(shape: str, n_tasks: int, n_task_types: int, *,
                     mean_eet: np.ndarray | None = None,
                     bandwidth: float | None = None, t0: float = 0.0,
                     slack: float = 4.0, slack_jitter: float = 0.0,
                     seed: int = 0) -> Workflow:
    """One Pegasus workflow of exactly ``n_tasks`` tasks (``montage``,
    ``cybershake`` or ``epigenomics``), each task's type its job type
    (``JOB_PROFILES[shape].jobs``), each edge the parent's output file.
    ``bandwidth`` is the shared storage's, in MB/s (None = no staging
    cost); it becomes MB per simulated time unit through the profile's
    ``seconds_per_unit``."""
    prof = JOB_PROFILES[shape]
    if n_task_types < len(prof.jobs):
        raise ValueError(f"{shape} has {len(prof.jobs)} job types, "
                         f"n_task_types is {n_task_types}")
    rng = np.random.default_rng(seed)
    types, parent_lists = _PEGASUS_DAGS[shape](n_tasks, rng)
    type_id = np.asarray(types, np.int32)
    src, _ = _edges_of(parent_lists)
    out_mb = np.asarray(prof.output_mb, np.float32)
    bw = None if bandwidth is None else \
        float(bandwidth) * prof.seconds_per_unit(n_tasks)
    return _assemble_workflow(parent_lists, n_task_types, mean_eet, t0,
                              slack, slack_jitter, rng, type_id=type_id,
                              nbytes=out_mb[type_id[src]], bandwidth=bw)


# Named DAG shapes with a common call shape, so experiment specs can
# treat "workflow shape" as a grid axis (launch/experiment.py):
# f(n_tasks, n_task_types, mean_eet, seed, bandwidth=None) -> Workflow
# (``bandwidth``: the shared storage's MB/s, which only shapes whose
# edges carry files take).  A shape in ``JOB_PROFILES`` types its tasks
# by job, and its EET rows follow the job runtimes.
WORKFLOW_GENERATORS = {
    "chain": lambda n, ntt, me, seed, bandwidth=None: chain_workflow(
        n, ntt, mean_eet=me, seed=seed),
    "fork_join": lambda n, ntt, me, seed, bandwidth=None: fork_join_workflow(
        max(n - 2, 1), 1, ntt, mean_eet=me, seed=seed),
    "map_reduce": lambda n, ntt, me, seed, bandwidth=None:
        map_reduce_workflow(max(n - max(n // 4, 1), 1), max(n // 4, 1),
                            ntt, mean_eet=me, seed=seed),
    "layered": lambda n, ntt, me, seed, bandwidth=None: layered_workflow(
        n, ntt, n_layers=4, mean_eet=me, seed=seed),
    **{name: (lambda n, ntt, me, seed, bandwidth=None, _s=name:
              pegasus_workflow(_s, n, ntt, mean_eet=me, bandwidth=bandwidth,
                               seed=seed))
       for name in JOB_PROFILES},
}


def register_workflow_generator(name: str, fn) -> None:
    """Register a custom DAG shape as a sweep axis value.

    ``fn(n_tasks, n_task_types, mean_eet, seed) -> Workflow``; a shape
    whose edges carry files also takes ``bandwidth=`` (MB/s).
    Registered names are immediately valid in
    ``experiment.WorkloadAxis(shapes=...)``; duplicates raise."""
    if name in WORKFLOW_GENERATORS:
        raise ValueError(f"workflow generator {name!r} already registered")
    WORKFLOW_GENERATORS[name] = fn


def resolve_shapes(names) -> tuple[str, ...]:
    """Validate DAG-shape names against the registry (the spec-consumable
    view of ``WORKFLOW_GENERATORS``)."""
    names = tuple(names)
    unknown = [n for n in names if n not in WORKFLOW_GENERATORS]
    if unknown:
        raise ValueError(f"unknown workflow generators {unknown}; known: "
                         f"{sorted(WORKFLOW_GENERATORS)}")
    return names


# ---------------------------------------------------------------------------
# Machine dynamics: availability traces + DVFS states
# ---------------------------------------------------------------------------
# Canonical DVFS operating points: (speed multiplier, power multiplier).
# Cubic-ish power-frequency relation: halving frequency cuts dynamic power
# far more than throughput.
DVFS_STATES: dict[str, tuple[float, float]] = {
    "nominal": (1.00, 1.00),
    "balanced": (0.80, 0.55),
    "powersave": (0.60, 0.30),
    "turbo": (1.20, 1.60),
}


def failure_trace(n_machines: int, n_intervals: int, *,
                  mtbf: float, mttr: float, t0: float = 0.0,
                  seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Alternating up/down renewal process per machine.

    Up durations ~ Exp(mtbf), down durations ~ Exp(mttr); returns
    ``(down_start, down_end)`` of shape (M, K), inf-padded — exactly the
    ``state.MachineDynamics`` encoding.  Use a huge ``mtbf`` for machines
    that never fail.
    """
    rng = np.random.default_rng(seed)
    down_start = np.full((n_machines, n_intervals), np.inf, np.float32)
    down_end = np.full((n_machines, n_intervals), np.inf, np.float32)
    for m in range(n_machines):
        t = t0
        for k in range(n_intervals):
            t += rng.exponential(mtbf)
            d = rng.exponential(mttr)
            down_start[m, k] = t
            down_end[m, k] = t + d
            t += d
    return down_start, down_end


@dataclass
class Scenario:
    """One simulation cell: workload + machine dynamics.

    ``speed``/``power_scale`` are per-machine DVFS multipliers (pick from
    ``DVFS_STATES`` or set freely), ``down_start``/``down_end`` the
    (M, K) availability trace, ``kill`` the per-machine eviction
    semantics (True = spot reclaim kills, False = fail/repair requeues).
    ``dynamics()`` converts to the device-side pytree the engine takes.
    """

    workload: Workload
    speed: np.ndarray           # (M,)
    power_scale: np.ndarray     # (M,)
    down_start: np.ndarray      # (M, K)
    down_end: np.ndarray        # (M, K)
    kill: np.ndarray            # (M,) bool
    name: str = ""

    def __post_init__(self):
        self.speed = np.asarray(self.speed, np.float32)
        self.power_scale = np.asarray(self.power_scale, np.float32)
        self.down_start = np.asarray(self.down_start, np.float32)
        self.down_end = np.asarray(self.down_end, np.float32)
        self.kill = np.asarray(self.kill, bool)

    @property
    def n_machines(self) -> int:
        return self.speed.shape[0]

    def host_dynamics(self):
        """The engine's ``MachineDynamics`` with numpy leaves: builds it
        on the host and starts no device work."""
        from repro.core.state import MachineDynamics
        return MachineDynamics(
            speed=self.speed,
            power_scale=self.power_scale,
            down_start=self.down_start,
            down_end=self.down_end,
            kill=self.kill,
        )

    def dynamics(self):
        """:meth:`host_dynamics` on the device."""
        import jax
        return jax.device_put(self.host_dynamics())


def make_scenario(workload: Workload, n_machines: int, *,
                  fail_rate: float = 0.0, mttr: float = 5.0,
                  spot: bool = False, dvfs: str | tuple[float, float]
                  = "nominal", n_intervals: int = 4,
                  seed: int = 0, name: str = "") -> Scenario:
    """Convenience scenario builder.

    ``fail_rate`` is failures per simulated second per machine (0 =
    always-up; mtbf = 1/fail_rate); ``spot`` selects kill semantics;
    ``dvfs`` names a ``DVFS_STATES`` entry (or gives an explicit
    (speed, power) pair) applied fleet-wide.
    """
    if isinstance(dvfs, str):
        speed_mult, power_mult = DVFS_STATES[dvfs]
    else:
        speed_mult, power_mult = dvfs
    if fail_rate > 0.0:
        down_start, down_end = failure_trace(
            n_machines, n_intervals, mtbf=1.0 / fail_rate, mttr=mttr,
            seed=seed)
    else:
        down_start = np.full((n_machines, n_intervals), np.inf, np.float32)
        down_end = np.full((n_machines, n_intervals), np.inf, np.float32)
    return Scenario(
        workload=workload,
        speed=np.full(n_machines, speed_mult, np.float32),
        power_scale=np.full(n_machines, power_mult, np.float32),
        down_start=down_start,
        down_end=down_end,
        kill=np.full(n_machines, spot, bool),
        name=name or (f"fail={fail_rate:g}" + ("/spot" if spot else "")
                      + f"/dvfs={dvfs}"),
    )


def load_workload_csv(path_or_text: str, *, n_task_types: int | None = None,
                      mean_eet: np.ndarray | None = None,
                      slack: float = 3.0) -> Workload:
    """Load an E2C trace: ``task_id,task_type,arrival_time[,deadline]``.

    task_type may be an integer id or a name (names are enumerated in order
    of first appearance).  If the deadline column is absent it is synthesized
    as ``arrival + slack * mean_eet[type]`` (E2C traces often omit it).
    """
    if os.path.exists(path_or_text):
        with open(path_or_text) as f:
            text = f.read()
    else:
        text = path_or_text
    rows = [r for r in csv.reader(io.StringIO(text)) if r and any(
        c.strip() for c in r)]
    start = 1 if not _is_float(rows[0][2]) else 0   # optional header
    names: dict[str, int] = {}
    type_id, arrival, deadline = [], [], []
    for r in rows[start:]:
        t = r[1].strip()
        if t.lstrip("-").isdigit():
            tid = int(t)
        else:
            tid = names.setdefault(t, len(names))
        type_id.append(tid)
        arrival.append(float(r[2]))
        deadline.append(float(r[3]) if len(r) > 3 and r[3].strip() else np.nan)
    arrival = np.asarray(arrival, np.float32)
    type_id = np.asarray(type_id, np.int32)
    deadline = np.asarray(deadline, np.float32)
    if np.any(np.isnan(deadline)):
        nt = n_task_types or (int(type_id.max()) + 1)
        me = mean_eet if mean_eet is not None else np.ones(nt, np.float32)
        synth = arrival + slack * me[type_id]
        deadline = np.where(np.isnan(deadline), synth, deadline)
    return Workload(arrival, type_id, deadline)


def save_workload_csv(w: Workload, path: str) -> None:
    with open(path, "w", newline="") as f:
        wr = csv.writer(f)
        wr.writerow(["task_id", "task_type", "arrival_time", "deadline"])
        for i in range(w.n_tasks):
            wr.writerow([i, int(w.type_id[i]), f"{w.arrival[i]:.6f}",
                         f"{w.deadline[i]:.6f}"])


def _is_float(s: str) -> bool:
    try:
        float(s)
        return True
    except ValueError:
        return False
