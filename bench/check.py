"""Decides ``correct``: the window's answers against the plain reference.

After the window closes, a sample of the replicas that the timed calls
finished, drawn from the seed (``per_policy`` replicas of every policy
the cell runs, over all its calls, by the family's layout), is simulated
again by the configuration's family (``families/<family>.py``: its
``draw`` and ``simulate``), independent of the program.  Four numbers
are compared, each with its limit:

  count_gap    largest |program - reference| in a replica's count of
               completed, missed, cancelled, preempted or requeued tasks
  value_gap    largest relative gap in a replica's makespan, energies,
               mean response, availability or completion rate
  replica_gap  replicas missing from a call's rows, plus, where the call
               folds a ``SweepAgg``, the gap between its per-policy
               replica counts and the grid's
  fold_gap     largest relative gap between the aggregate's exact
               per-policy sums, minima and maxima and a plain exact fold
               (``math.fsum``) of the rows it was folded from
"""
from __future__ import annotations

import math
import multiprocessing as mp
import os

import numpy as np

from bench import harness as H

#: each limit lies between the largest reading of sound runs of the
#: program and the smallest reading of the bfloat16 control (PERF.md)
LIMITS = {"count_gap": 0.0, "value_gap": 1e-3, "replica_gap": 0.0,
          "fold_gap": 0.0}
TERMINAL = ("completed", "missed", "cancelled", "preempted")
#: most host processes the reference runs on at once
MAX_WORKERS = 12


def draw_sample(family, axes: dict, n_replicas: int, n_calls: int,
                per_policy: int, seed: int) -> list[tuple[int, int]]:
    """(call, replica) pairs: ``per_policy`` of each policy, from the seed."""
    rng = np.random.default_rng([seed, 0xC4EC])
    pol = family.replica_policies(axes, n_replicas)
    out = []
    for p in range(len(axes["policies"])):
        cand = [(c, int(r)) for c in range(n_calls)
                for r in np.nonzero(pol == p)[0]]
        pick = rng.choice(len(cand), size=min(per_policy, len(cand)),
                          replace=False)
        out += [cand[i] for i in sorted(pick)]
    return out


def _ref_row(job):
    bench_dir, config, axes, seed, r, window, precision = job
    family = H.family(config, bench_dir)
    inp, policy = family.draw(config, axes, seed, r)
    return family.simulate(inp, policy, window=window, precision=precision)


def reference_rows(config: dict, axes: dict, jobs: list[tuple[int, int]],
                   window: int | None, precision: str,
                   workers: int | None = None) -> list[dict]:
    """The reference's row for each (seed, replica) job, on host workers
    that load the configuration's family by name and import only numpy
    (spawned, so none touches the chip)."""
    todo = [(H.BENCH_DIR, config, axes, s, r, window, precision)
            for s, r in jobs]
    workers = workers or max(1, min(len(todo), (os.cpu_count() or 2) - 1,
                                    MAX_WORKERS))
    if workers == 1:
        return [_ref_row(j) for j in todo]
    with mp.get_context("spawn").Pool(workers) as pool:
        out = pool.map(_ref_row, todo, chunksize=1)
        pool.close()
        pool.join()
    return out


def row_gaps(family, prog: dict, ref: dict) -> tuple[float, float]:
    """(count gap, relative value gap) between one program row and the
    reference's row for the same replica."""
    cg = [abs(float(prog[k]) - ref[k]) for k in family.COUNT_COLUMNS]
    vg = [abs(float(prog[k]) - ref[k]) / max(abs(ref[k]), 1e-3)
          for k in family.VALUE_COLUMNS]
    # a missing or NaN answer is as far off as it gets
    return (float(np.max(cg)) if np.all(np.isfinite(cg)) else math.inf,
            float(np.max(vg)) if np.all(np.isfinite(vg)) else math.inf)


def fold_gaps(family, rows: dict, agg, axes: dict, n_replicas: int
              ) -> tuple[float, float]:
    """(replica-count gap, relative fold gap) of one call's ``SweepAgg``."""
    pol = family.replica_policies(axes, n_replicas)
    count_gap = 0.0
    gap = 0.0
    for p, name in enumerate(axes["policies"]):
        sel = pol == p
        count_gap += abs(agg.count(name) - int(sel.sum()))
        for k in agg.columns:
            if k not in rows or len(rows[k]) != n_replicas:
                gap = max(gap, 1.0)
                continue
            x = np.asarray(rows[k], np.float32)[sel]
            want = math.fsum(float(v) for v in x)
            got = agg.total(k, name)
            gap = max(gap, abs(got - want) / max(abs(want), 1e-30))
            if x.size and (agg.min(k, name) != float(x.min())
                           or agg.max(k, name) != float(x.max())):
                gap = max(gap, 1.0)
    return count_gap, gap


def compare(calls: list, config: dict, traffic: dict, seed: int,
            control: str | None = None, workers: int | None = None) -> dict:
    """-> {number: value} for the window's calls.

    ``calls`` holds, per finished call, ``(seed, rows, agg)``: the call's
    seed, its per-replica rows ({column: (R,) array}) and its aggregate
    (None where the path folds none).  The reference runs in the
    precision the configuration states.  ``control`` names a lower
    precision in which the reference takes the program's place."""
    family = H.family(config)
    axes = family.axes(config, traffic)
    n_rep = traffic["replicas"]
    window = traffic.get("streaming")
    sample = draw_sample(family, axes, n_rep, len(calls),
                         traffic["check_per_policy"], seed)
    jobs = [(calls[c][0], r) for c, r in sample]
    refs = reference_rows(config, axes, jobs, window, config["precision"],
                          workers)
    if control is None:
        progs = []
        for (c, r) in sample:
            rows = calls[c][1]
            progs.append({k: np.asarray(rows[k])[r]
                          if r < len(rows[k]) else np.nan
                          for k in family.COUNT_COLUMNS
                          + family.VALUE_COLUMNS})
    else:
        progs = reference_rows(config, axes, jobs, window, control, workers)
    out = {"count_gap": 0.0, "value_gap": 0.0, "replica_gap": 0.0}
    for prog, ref in zip(progs, refs):
        cg, vg = row_gaps(family, prog, ref)
        out["count_gap"] = max(out["count_gap"], cg)
        out["value_gap"] = max(out["value_gap"], vg)
    if control is None:
        for _, rows, agg in calls:
            out["replica_gap"] += abs(n_rep - min(len(v) for v in
                                                  rows.values()))
            if agg is not None:
                cg, fg = fold_gaps(family, rows, agg, axes, n_rep)
                out["replica_gap"] += cg
                out["fold_gap"] = max(out.get("fold_gap", 0.0), fg)
    for k, v in out.items():
        if math.isnan(v):
            out[k] = math.inf
    return out


def verdict(numbers: dict) -> bool:
    return all(v <= LIMITS[k] for k, v in numbers.items())
