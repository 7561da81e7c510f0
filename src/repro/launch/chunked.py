"""Pod-scale Monte-Carlo: chunked, donated, device-reduced sweeps.

``run_experiment`` materializes the whole grid and lands one result row
per replica on host — fine at 10^4 replicas, hopeless at the 10^6-point
scenario grids ROADMAP item 3 asks for.  This module is the scale path
(docs/scaling.md):

  chunk     the replica axis is split into fixed-size chunks; each chunk
            is normalized on host (:func:`experiment.normalize_chunk` —
            per-replica RNG substreams make the grid random-access, so a
            chunk's draws are bitwise those of the monolithic grid) and
            executed *through the existing cached executable*
            (:func:`experiment.compile_sweep`), wrapped in a jitted step
            that **donates** the running aggregate
            (``jax.jit(..., donate_argnums=0)``), so chunk N+1's fold
            writes in place of chunk N's.
  reduce    the step folds each chunk's per-replica metrics into a
            ``SweepAgg`` pytree on device — per report column and per
            policy: count, min, max, a log-bucket histogram on
            ``core/metrics.py`` bucket edges, and an **exact** sum.
            Per-replica results never land on host unless
            ``keep_replicas=True``.
  overlap   an async double-buffered driver dispatches chunk N, then
            normalizes chunk N+1 on host while the device runs chunk N
            (the normalize starts no device work until the chunk's one
            transfer per input leaf), and only then blocks
            (``jax.block_until_ready``) on chunk N-1 — at most two
            chunks in flight.  ``core/telemetry.py`` spans record the
            timeline, on the profiler's clock too (``e2c.`` annotations),
            so a trace shows whether the normalize actually hid.

Exact summation — why the aggregate is bitwise partition-invariant
------------------------------------------------------------------
Floating-point addition is not associative, so a naive ``sum`` would
make the aggregate depend on the chunk size.  Instead each float32
sample is decomposed into its sign-carrying 25-bit mantissa and biased
exponent (a bitcast, no rounding), and mantissas are summed as exact
integers in per-exponent bins: a ``(n_policy, 256)`` accumulator whose
entries are 64-bit integers emulated as an ``(int32 hi, uint32 lo)``
pair (jax's default x64-disabled mode has no int64).  Integer addition
is associative and commutative and the representation is canonical, so
folding chunks in any order or partition yields the *identical*
accumulator; the finalize step reconstructs ``sum = Σ_b mant_b·2^(b-150)``
in Python big-ints and rounds once to float.  The scatter pieces are
12-bit mantissa halves, so one chunk of up to 2^18 replicas sums without
int32 overflow (:data:`MAX_CHUNK`).
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics as ME
from repro.core import schedulers as P
from repro.core import telemetry as TL
from repro.launch import experiment as X

__all__ = [
    "SWEEP_SPEC", "MAX_CHUNK", "ColumnAgg", "SweepAgg", "ChunkedStats",
    "aggregate_metrics", "run_chunked_experiment",
]

#: log-bucket geometry of the per-column histograms (reuses the
#: core/metrics.py edge construction; wide, because report columns span
#: counts, seconds and joules).
SWEEP_SPEC = ME.MetricsSpec(buckets=64, lo=1e-4, hi=1e7)

#: largest chunk whose 12-bit mantissa pieces sum without int32 overflow
#: in the per-chunk scatter (2^18 · 2^12 = 2^30 < 2^31).
MAX_CHUNK = 1 << 18


# ---------------------------------------------------------------------------
# SweepAgg device pytree: per-column accumulators
# ---------------------------------------------------------------------------
class ColumnAgg(NamedTuple):
    """Device accumulator for ONE report column (leading policy axis P).

    ``a_*``/``b_*`` are the exact mantissa sums: per biased-exponent bin,
    the high (``mant >> 12``) and low (``mant & 0xfff``) mantissa pieces
    summed as emulated 64-bit integers (``hi`` int32, ``lo`` uint32)."""
    a_hi: jnp.ndarray   # (P, 256) int32
    a_lo: jnp.ndarray   # (P, 256) uint32
    b_hi: jnp.ndarray   # (P, 256) int32
    b_lo: jnp.ndarray   # (P, 256) uint32
    count: jnp.ndarray  # (P,)     int32
    vmin: jnp.ndarray   # (P,)     float32
    vmax: jnp.ndarray   # (P,)     float32
    hist: jnp.ndarray   # (P, B+2) int32 — SWEEP_SPEC log buckets


def _init_column(n_policy: int, aspec: ME.MetricsSpec) -> ColumnAgg:
    z = np.zeros((n_policy, 256), np.int32)
    u = np.zeros((n_policy, 256), np.uint32)
    return ColumnAgg(
        a_hi=z, a_lo=u, b_hi=z.copy(), b_lo=u.copy(),
        count=np.zeros((n_policy,), np.int32),
        vmin=np.full((n_policy,), np.inf, np.float32),
        vmax=np.full((n_policy,), -np.inf, np.float32),
        hist=np.zeros((n_policy, aspec.buckets + 2), np.int32))


def _decompose(x: jnp.ndarray):
    """float32 -> (signed 25-bit mantissa, exponent bin in [1, 255]).

    ``value == mant · 2^(bin - 150)`` exactly: normals carry the hidden
    bit, subnormals (biased exponent 0) share bin 1's scale."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bexp = ((u >> 23) & 0xFF).astype(jnp.int32)
    frac = (u & 0x7FFFFF).astype(jnp.int32)
    mant = jnp.where(bexp > 0, frac | (1 << 23), frac)
    mant = jnp.where((u >> 31) == 1, -mant, mant)
    return mant, jnp.maximum(bexp, 1)


def _acc64(hi: jnp.ndarray, lo: jnp.ndarray, add: jnp.ndarray):
    """(hi int32, lo uint32) += add (int32), exact mod 2^64."""
    alo = add.astype(jnp.uint32)
    nlo = lo + alo
    carry = jnp.where(nlo < lo, 1, 0).astype(jnp.int32)
    return hi + (add >> 31) + carry, nlo


def _fold_column(col: ColumnAgg, x: jnp.ndarray, pol_idx: jnp.ndarray,
                 aspec: ME.MetricsSpec) -> ColumnAgg:
    """Fold one chunk's (C,) column samples into the accumulator."""
    xf = x.astype(jnp.float32)
    mant, ebin = _decompose(xf)
    n_policy = col.count.shape[0]
    pa = jnp.zeros((n_policy, 256), jnp.int32
                   ).at[pol_idx, ebin].add(mant >> 12)
    pb = jnp.zeros((n_policy, 256), jnp.int32
                   ).at[pol_idx, ebin].add(mant & 0xFFF)
    a_hi, a_lo = _acc64(col.a_hi, col.a_lo, pa)
    b_hi, b_lo = _acc64(col.b_hi, col.b_lo, pb)
    return ColumnAgg(
        a_hi, a_lo, b_hi, b_lo,
        count=col.count.at[pol_idx].add(1),
        vmin=col.vmin.at[pol_idx].min(xf),
        vmax=col.vmax.at[pol_idx].max(xf),
        hist=col.hist.at[pol_idx, ME._bucket(aspec, xf)].add(1))


def _fold(cols: dict, metrics: dict, pol_idx: jnp.ndarray,
          aspec: ME.MetricsSpec) -> dict:
    return {k: _fold_column(cols[k], metrics[k], pol_idx, aspec)
            for k in cols}


_FOLD_JIT = jax.jit(_fold, static_argnames="aspec")


# ---------------------------------------------------------------------------
# Host-side finalized aggregate
# ---------------------------------------------------------------------------
def _comb64(hi, lo) -> np.ndarray:
    """Recombine the emulated pair into exact int64 (host side)."""
    return (np.asarray(hi, np.int64) << 32) + np.asarray(lo, np.int64)


def _exact_total(a_row: np.ndarray, b_row: np.ndarray) -> float:
    """Σ_bin (a·2^12 + b)·2^(bin-150) in Python big-ints, rounded once."""
    n = 0
    for i in np.nonzero(a_row | b_row)[0]:
        n += ((int(a_row[i]) << 12) + int(b_row[i])) << int(i)
    return math.ldexp(float(n), -150) if n else 0.0


@dataclass
class SweepAgg:
    """Finalized (host) sweep aggregate: exact per-policy column stats.

    ``a``/``b`` are the exact int64 mantissa-piece sums per exponent bin
    (see module docstring); two aggregates over the same replicas are
    bitwise-equal regardless of how the replicas were chunked or
    ordered.  ``quantile`` reconstructs tails from the log-bucket
    histogram via the shared :func:`repro.core.metrics.hist_quantile`.
    """
    policies: tuple[str, ...]
    spec: ME.MetricsSpec
    a: dict[str, np.ndarray]        # (P, 256) int64
    b: dict[str, np.ndarray]        # (P, 256) int64
    counts: np.ndarray              # (P,) int64
    vmin: dict[str, np.ndarray]     # (P,) float32
    vmax: dict[str, np.ndarray]     # (P,) float32
    hist: dict[str, np.ndarray]     # (P, B+2) int64

    @classmethod
    def from_device(cls, cols: dict, policies: tuple[str, ...],
                    aspec: ME.MetricsSpec) -> "SweepAgg":
        cols = jax.device_get(cols)
        first = next(iter(cols.values()))
        return cls(
            policies=tuple(policies), spec=aspec,
            a={k: _comb64(c.a_hi, c.a_lo) for k, c in cols.items()},
            b={k: _comb64(c.b_hi, c.b_lo) for k, c in cols.items()},
            counts=np.asarray(first.count, np.int64),
            vmin={k: np.asarray(c.vmin) for k, c in cols.items()},
            vmax={k: np.asarray(c.vmax) for k, c in cols.items()},
            hist={k: np.asarray(c.hist, np.int64)
                  for k, c in cols.items()})

    # -- accessors --------------------------------------------------------
    @property
    def columns(self) -> tuple[str, ...]:
        return tuple(self.a)

    def _p(self, policy: str | None) -> int | None:
        return None if policy is None else self.policies.index(policy)

    def count(self, policy: str | None = None) -> int:
        p = self._p(policy)
        return int(self.counts.sum() if p is None else self.counts[p])

    def total(self, col: str, policy: str | None = None) -> float:
        """Exact sum of the column (correctly rounded to float)."""
        p = self._p(policy)
        a, b = self.a[col], self.b[col]
        if p is None:
            a, b = a.sum(axis=0), b.sum(axis=0)
        else:
            a, b = a[p], b[p]
        return _exact_total(a, b)

    def mean(self, col: str, policy: str | None = None) -> float:
        n = self.count(policy)
        return self.total(col, policy) / n if n else 0.0

    def min(self, col: str, policy: str | None = None) -> float:
        p = self._p(policy)
        v = self.vmin[col]
        return float(v.min() if p is None else v[p])

    def max(self, col: str, policy: str | None = None) -> float:
        p = self._p(policy)
        v = self.vmax[col]
        return float(v.max() if p is None else v[p])

    def quantile(self, col: str, q: float,
                 policy: str | None = None) -> float:
        p = self._p(policy)
        h = self.hist[col]
        h = h.sum(axis=0) if p is None else h[p]
        return ME.hist_quantile(h, self.spec, q)

    def column(self, col: str, policy: str | None = None) -> dict:
        return {"count": self.count(policy),
                "mean": self.mean(col, policy),
                "min": self.min(col, policy),
                "max": self.max(col, policy),
                "p50": self.quantile(col, 50.0, policy),
                "p95": self.quantile(col, 95.0, policy),
                "p99": self.quantile(col, 99.0, policy)}

    def summary(self, policy: str | None = None) -> dict:
        """{column: {count, mean, min, max, p50, p95, p99}} — the same
        stats ``report.summarize`` feeds per run, off the aggregate."""
        return {k: self.column(k, policy) for k in self.columns}

    def by_policy(self, keys: tuple[str, ...]) -> list[dict]:
        """Per-policy mean rows, shaped like
        :meth:`experiment.ExperimentResult.by_policy` (exact means)."""
        return [dict({"policy": pol, "replicas": self.count(pol)},
                     **{k: self.mean(k, pol) for k in keys})
                for pol in self.policies]

    def merge(self, other: "SweepAgg") -> "SweepAgg":
        """Exact fold of two disjoint aggregates (host side)."""
        if (self.policies != other.policies or self.spec != other.spec
                or self.columns != other.columns):
            raise ValueError("aggregates are not over the same grid shape")
        return SweepAgg(
            policies=self.policies, spec=self.spec,
            a={k: self.a[k] + other.a[k] for k in self.a},
            b={k: self.b[k] + other.b[k] for k in self.b},
            counts=self.counts + other.counts,
            vmin={k: np.minimum(self.vmin[k], other.vmin[k])
                  for k in self.vmin},
            vmax={k: np.maximum(self.vmax[k], other.vmax[k])
                  for k in self.vmax},
            hist={k: self.hist[k] + other.hist[k] for k in self.hist})


# ---------------------------------------------------------------------------
# Chunk step: cached executable + on-device fold, donated buffers
# ---------------------------------------------------------------------------
def _policy_index(policies: tuple[str, ...], policy_ids) -> np.ndarray:
    """Map replica policy ids -> position in the spec's policy tuple."""
    lut = np.full(max(P.POLICY_IDS.values()) + 1, -1, np.int32)
    for i, pol in enumerate(policies):
        lut[P.POLICY_IDS[pol]] = i
    idx = lut[np.asarray(policy_ids)]
    if (idx < 0).any():
        raise ValueError("replicas carry policy ids outside the spec's "
                         "policy axis")
    return idx


def _compile_chunk_step(params, aspec: ME.MetricsSpec, streaming: bool,
                        keep: bool) -> Callable:
    """The jitted chunk step for ``params``, cached in the experiment
    layer's executable cache (same economics as ``compile_sweep``; the
    wrapped sweep IS the ``compile_sweep`` executable, inlined).

    ``step(cols, pol_idx, args, policy_params) -> (cols', metrics|None,
    token)`` — ``cols`` is donated and aliases ``cols'`` (the only
    output of matching shape, so the only donation XLA can use); the
    chunk inputs are freed when the driver drops them.  ``token`` is a
    fresh tiny array (not aliased to ``cols'``) the driver can block on
    after the accumulator has been donated onward."""
    key = ("chunked", params, aspec, streaming, keep)
    fn = X._EXEC_CACHE.get(key)
    if fn is not None:
        X._CACHE_STATS["hits"] += 1
        return fn
    inner = (X.compile_stream_sweep(params) if streaming
             else X.compile_sweep(params))
    X._CACHE_STATS["misses"] += 1

    def step(cols, pol_idx, args, policy_params):
        m = inner(*args, policy_params)
        out = _fold(cols, m, pol_idx, aspec)
        token = next(iter(out.values())).count.sum()
        return out, (m if keep else None), token

    fn = jax.jit(step, donate_argnums=0)
    X._EXEC_CACHE[key] = fn
    return fn


def aggregate_metrics(metrics: dict, policy_ids,
                      policies: tuple[str, ...],
                      aspec: ME.MetricsSpec = SWEEP_SPEC) -> SweepAgg:
    """Fold an already-materialized per-replica metrics dict (a
    monolithic ``run_experiment`` result) into a :class:`SweepAgg` — the
    reference the chunked path is parity-tested against."""
    pol_idx = _policy_index(tuple(policies), policy_ids)
    if pol_idx.shape[0] > MAX_CHUNK:
        raise ValueError(f"aggregate_metrics folds at most {MAX_CHUNK} "
                         f"replicas at once; got {pol_idx.shape[0]}")
    cols = {k: _init_column(len(policies), aspec) for k in metrics}
    cols = _FOLD_JIT(cols, metrics, jnp.asarray(pol_idx), aspec)
    return SweepAgg.from_device(cols, tuple(policies), aspec)


# ---------------------------------------------------------------------------
# The async double-buffered driver
# ---------------------------------------------------------------------------
@dataclass
class ChunkedStats:
    """Driver timing: where the wall-clock of a chunked run went, by
    host stage.  Whether a normalize hid behind device work is a
    question for a device trace (the ``e2c.chunk_normalize`` spans
    beside the device ops), not for host clocks."""
    chunk: int
    n_chunks: int
    normalize_s: float = 0.0
    dispatch_s: float = 0.0
    sync_s: float = 0.0
    wall_s: float = 0.0


def run_chunked_experiment(spec, chunk: int, *, mesh=None,
                           policy_params=None, replicas=None,
                           keep_replicas: bool = False,
                           on_chunk: Callable[[int], None] | None = None,
                           aspec: ME.MetricsSpec = SWEEP_SPEC):
    """Chunked/donated/device-reduced twin of ``run_experiment`` —
    normally reached as ``run_experiment(spec, chunk=...)``.

    Pipeline per chunk ``c``: dispatch ``step(c)`` (async), normalize
    chunk ``c+1`` on host while the device runs, block on chunk
    ``c-1``'s completion token — at most two chunks in flight, live
    device buffers O(chunk).  ``on_chunk(c)`` fires after chunk ``c``
    retires (memory-accounting hook).  Returns an
    ``experiment.ExperimentResult`` whose ``agg`` is the
    :class:`SweepAgg`; ``metrics`` holds stacked host copies only under
    ``keep_replicas=True``.
    """
    chunk = int(chunk)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if chunk > MAX_CHUNK:
        raise ValueError(f"chunk must be <= {MAX_CHUNK} (exact-sum "
                         f"scatter bound), got {chunk}")
    if spec.sim_params.trace:
        raise ValueError("trace=True is O(R) host memory — incompatible "
                         "with chunked execution")
    n_rep = spec.n_replicas
    if replicas is not None and replicas.n_replicas != n_rep:
        raise ValueError(f"replicas carry {replicas.n_replicas} rows, "
                         f"spec asks for {n_rep}")
    n_chunks = -(-n_rep // chunk)
    policies = spec.policy.policies
    params = spec.stream_params if spec.streaming else spec.sim_params
    if mesh is not None:
        from repro.launch.mesh import mesh_device_count
        n_dev = mesh_device_count(mesh)
        last = n_rep - (n_chunks - 1) * chunk
        if chunk % n_dev or last % n_dev:
            raise ValueError(f"chunk sizes {chunk}/{last} must divide "
                             f"over {n_dev} devices")

    def materialize(lo: int, hi: int):
        """Chunk ``[lo, hi)``'s step inputs, built on the host and moved
        to the device in one ``device_put``: its only device work."""
        if replicas is not None:
            reps = jax.tree.map(lambda x: x[lo:hi], replicas)
        else:
            reps = X.normalize_chunk(spec, lo, hi)
        with TL.span("stack") as sp:
            inputs = (_policy_index(policies, reps.policy_ids),
                      X.sweep_args(spec, reps))
            sp.update(X.h2d(inputs))
            if mesh is not None:
                from repro.launch.mesh import put_chunk
                return put_chunk(inputs, mesh, hi - lo)
            return jax.device_put(inputs)

    stats = ChunkedStats(chunk=chunk, n_chunks=n_chunks)
    step = _compile_chunk_step(params, aspec, spec.streaming,
                               keep_replicas)
    kept: list = []
    pending: list = []   # [(chunk idx, completion token, metrics|None)]

    def retire(sp_attrs=()):
        c, token, m = pending.pop(0)
        t0 = time.perf_counter()
        with TL.span("chunk_sync", chunk=c):
            jax.block_until_ready(token)
        stats.sync_s += time.perf_counter() - t0
        if m is not None:
            kept.append(jax.tree.map(np.asarray, m))
        if on_chunk is not None:
            on_chunk(c)

    t_wall = time.perf_counter()
    with TL.span("experiment", chunked=True, chunk=chunk,
                 n_chunks=n_chunks, n_replicas=n_rep,
                 streaming=bool(spec.streaming),
                 policies=policies, backend=jax.default_backend()) as xsp:
        hi = min(chunk, n_rep)
        t0 = time.perf_counter()
        with TL.span("chunk_normalize", chunk=0, n_replicas=hi,
                     overlapped=False):
            cur = materialize(0, hi)
        stats.normalize_s += time.perf_counter() - t0
        cols = {}
        for c in range(n_chunks):
            if c == 0:
                keys = jax.eval_shape(
                    X.compile_experiment(spec), *cur[1], policy_params)
                cols = {k: _init_column(len(policies), aspec) for k in keys}
            while len(pending) > 1:   # retire everything but c-1
                retire()
            pol_idx, args = cur
            cur = None                # donated below — drop the refs
            t0 = time.perf_counter()
            with TL.span("chunk_dispatch", chunk=c):
                cols, m, token = step(cols, pol_idx, args, policy_params)
            stats.dispatch_s += time.perf_counter() - t0
            pending.append((c, token, m))
            if c + 1 < n_chunks:
                lo = (c + 1) * chunk
                hi = min(lo + chunk, n_rep)
                t0 = time.perf_counter()
                with TL.span("chunk_normalize", chunk=c + 1,
                             n_replicas=hi - lo, overlapped=True):
                    cur = materialize(lo, hi)
                stats.normalize_s += time.perf_counter() - t0
        while pending:
            retire()
        agg = SweepAgg.from_device(cols, policies, aspec)
        stats.wall_s = time.perf_counter() - t_wall
        xsp.update(normalize_s=round(stats.normalize_s, 6),
                   dispatch_s=round(stats.dispatch_s, 6),
                   sync_s=round(stats.sync_s, 6),
                   retraces=X._CACHE_STATS["retraces"])
        TL.event("cache", **X.cache_stats())
    metrics = None
    if keep_replicas:
        metrics = jax.tree.map(
            lambda *xs: np.concatenate(xs, axis=0), *kept)
    return X.ExperimentResult(spec=spec, replicas=None, metrics=metrics,
                              traces=None, agg=agg, chunked=stats)
