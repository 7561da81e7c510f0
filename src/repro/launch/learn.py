"""Learned-scheduling evaluation harness: train on one scenario grid,
evaluate on a held-out grid, report learned-vs-heuristic scoreboards.

The workflow (docs/learned_scheduling.md):

  1. ``grid_spec`` declares a (failure-rate × DVFS × arrival-pattern)
     scenario grid as an ``ExperimentSpec`` (docs/experiments.md); its
     normalized form is the stacked 5-tuple the sweeps take, with the
     policy-id column left as a placeholder because the grid is
     re-swept once per policy.  (``make_grid`` is the deprecated
     tuple-returning shim.)
  2. ``core.train_policy.train`` runs antithetic ES on the training grid
     (one jitted call per generation, (2·pop+1) × S replicas each).
  3. ``scoreboard`` re-evaluates every heuristic plus the trained
     policies on the *held-out* grid (different seeds AND a different
     arrival-pattern mixture) and returns one row per policy.
  4. ``viz.policy_scoreboard`` renders the rows; ``main`` writes
     ``results/learned/scoreboard.{json,html}``.

Run it:  PYTHONPATH=src python -m repro.launch.learn --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as E
from repro.core import neural as NN
from repro.core import schedulers as P
from repro.core import train_policy as TP
from repro.core import viz
from repro.launch.experiment import (ExperimentSpec, FleetAxis, PolicyAxis,
                                     ScenarioAxis, WorkloadAxis,
                                     compile_sweep, normalize)

BASELINES = ["fcfs", "rr", "met", "mct", "ee_met", "ee_mct", "minmin",
             "maxmin", "edf_mct"]


def grid_spec(n_replicas: int, n_tasks: int, n_machines: int, *,
              n_task_types: int = 4, n_machine_types: int = 3,
              fail_rates=(0.0, 0.1), dvfs_states=("nominal", "powersave"),
              arrivals=("poisson", "bursty"), rate: float = 4.0,
              spot_frac: float = 0.5, mttr: float = 4.0,
              n_intervals: int = 4, seed: int = 0) -> ExperimentSpec:
    """(failure-rate × DVFS × arrival-pattern) evaluation grid as a spec.

    The policy axis is pinned to a single placeholder (``mct``), so the
    arrival pattern — replica ``r`` gets ``arrivals[(r // (F·D)) % A]``
    — is the third grid axis and evaluation re-sweeps the *same*
    normalized grid once per policy, which is what makes the comparison
    paired (identical scenarios for every policy).
    """
    return ExperimentSpec(
        n_replicas, FleetAxis(n_machines, n_machine_types),
        WorkloadAxis(n_tasks, n_task_types, rate, arrivals=tuple(arrivals)),
        scenario=ScenarioAxis(tuple(fail_rates), tuple(dvfs_states),
                              spot_frac, mttr, n_intervals),
        policy=PolicyAxis(("mct",)), seed=seed)


def make_grid(n_replicas: int, n_tasks: int, n_machines: int,
              **kw) -> tuple:
    """DEPRECATED shim -> ``normalize(grid_spec(...)).legacy()``."""
    from repro.launch.sim import _deprecated
    _deprecated("make_grid", "normalize(learn.grid_spec(...))")
    return jax.device_put(normalize(grid_spec(n_replicas, n_tasks,
                                              n_machines, **kw))).legacy()


def scoreboard(inputs: tuple, policies: list[str],
               trained: dict[str, NN.PolicyParams] | None = None,
               sim_params: E.SimParams = E.SimParams(),
               energy_weight: float = 0.2,
               e_scale: float | None = None
               ) -> tuple[list[dict], float]:
    """-> (rows, e_scale): one row per policy, sorted best-first, with
    mean score + metrics on a paired grid.

    ``trained`` maps learned-policy names to their weights; heuristics in
    ``policies`` run with the engine default.  ``e_scale`` defaults to
    MCT's grid-mean energy (same normalization as training), computed
    from the sweep this function runs anyway — every policy's grid is
    swept exactly once.
    """
    from repro.launch.experiment import Replicas
    if isinstance(inputs, Replicas):
        inputs = inputs.legacy()
    tt, mt, tb, _pids, dyn = inputs
    n_rep = int(tt.arrival.shape[0])
    trained = trained or {}
    # one cached executable serves both the heuristic and the learned
    # sweeps (jax specializes per policy-params structure inside it)
    sweep = compile_sweep(sim_params)
    metrics: dict[str, dict] = {}
    for pol in policies:
        pids = jnp.full((n_rep,), P.POLICY_IDS[pol], jnp.int32)
        metrics[pol] = sweep(tt, mt, tb, pids, dyn, None,
                             trained.get(pol))
    if e_scale is None:
        ref = metrics.get("mct") or next(iter(metrics.values()))
        e_scale = float(np.mean(np.asarray(ref["energy"])))
    rows = []
    for pol, m in metrics.items():
        score = np.asarray(TP.miss_energy_score(
            m, jnp.float32(e_scale), energy_weight))
        rows.append({
            "policy": pol + ("*" if pol in trained else ""),
            "score": round(float(score.mean()), 4),
            "completion_rate": round(float(np.mean(
                np.asarray(m["completion_rate"]))), 4),
            "missed": round(float(np.mean(
                np.asarray(m["missed"]) + np.asarray(m["cancelled"])
                + np.asarray(m["preempted"]))), 2),
            "energy": round(float(np.mean(np.asarray(m["energy"]))), 1),
            "makespan": round(float(np.mean(
                np.asarray(m["makespan"]))), 2),
        })
    return sorted(rows, key=lambda r: r["score"]), e_scale


def train_and_evaluate(*, n_train: int = 16, n_test: int = 16,
                       n_tasks: int = 48, n_machines: int = 6,
                       cfg: TP.ESConfig = TP.ESConfig(),
                       policies: list[str] = ("mlp",),
                       baselines: list[str] = BASELINES,
                       sim_params: E.SimParams = E.SimParams(),
                       seed: int = 0, out_dir: str | None = None) -> dict:
    """Full harness: train on one grid, scoreboard on a held-out grid.

    The held-out grid uses different seeds AND a different arrival
    mixture (adds ``diurnal``/``onoff`` processes the training grid never
    saw) — the generalization axis the paper's scenario studies sweep.
    """
    t0 = time.perf_counter()
    train_grid = jax.device_put(normalize(grid_spec(
        n_train, n_tasks, n_machines, arrivals=("poisson", "bursty"),
        seed=seed))).legacy()
    test_grid = jax.device_put(normalize(grid_spec(
        n_test, n_tasks, n_machines,
        arrivals=("poisson", "diurnal", "onoff"),
        seed=seed + 10_000))).legacy()
    trained, train_hist = {}, {}
    for pol in policies:
        res = TP.train(train_grid, policy=pol, sim_params=sim_params,
                       cfg=cfg)
        trained[pol] = res.params
        train_hist[pol] = res.history
    rows, e_scale = scoreboard(test_grid, list(baselines) + list(policies),
                               trained, sim_params, cfg.energy_weight)
    payload = {
        "rows": rows, "e_scale": e_scale,
        "history": train_hist,
        "config": {"pop": cfg.pop, "sigma": cfg.sigma, "lr": cfg.lr,
                   "generations": cfg.generations,
                   "energy_weight": cfg.energy_weight,
                   "n_train": n_train, "n_test": n_test,
                   "n_tasks": n_tasks, "n_machines": n_machines,
                   "seed": seed},
        "seconds": round(time.perf_counter() - t0, 2),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "scoreboard.json"), "w") as f:
            json.dump(payload, f, indent=1)
        svg = viz.policy_scoreboard(rows)
        viz.save(os.path.join(out_dir, "scoreboard.svg"), svg)
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny budget (CI): few generations, small fleet")
    ap.add_argument("--generations", type=int, default=None)
    ap.add_argument("--pop", type=int, default=None)
    ap.add_argument("--out", default="results/learned")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.smoke:
        kw = dict(n_train=6, n_test=6, n_tasks=24, n_machines=4)
    else:
        kw = dict(n_train=24, n_test=24, n_tasks=64, n_machines=8)
    pop = args.pop if args.pop is not None else (4 if args.smoke else 12)
    gens = args.generations if args.generations is not None \
        else (3 if args.smoke else 30)
    cfg = TP.ESConfig(pop=pop, generations=gens, seed=args.seed)
    payload = train_and_evaluate(cfg=cfg, out_dir=args.out, seed=args.seed,
                                 **kw)
    print(f"# learned-vs-heuristic scoreboard (held-out grid, "
          f"{payload['seconds']}s)")
    cols = ["policy", "score", "completion_rate", "missed", "energy",
            "makespan"]
    print(" | ".join(cols))
    for r in payload["rows"]:
        print(" | ".join(str(r[c]) for c in cols))
    print(f"\nwrote {args.out}/scoreboard.json (+ .svg)")


if __name__ == "__main__":
    main()
