"""EET (Expected Execution Time) matrix utilities.

The EET matrix is how E2C models heterogeneity: ``eet[task_type,
machine_type]`` is the expected execution time of a task of a given type on a
machine of a given type (paper Fig. 2 — user-editable, CSV loadable).

We keep the E2C CSV convention: a header row of machine-type names, one row
per task type, first column the task-type name::

    task_type,  m0, m1, ...
    obj_det,   3.2, 0.9, ...

plus helpers to synthesize EET matrices with controlled heterogeneity
(machine/task "consistency" in the HC-scheduling sense) and to derive EET rows
from compiled roofline terms of real models (the FELARE [12] use-case).
"""
from __future__ import annotations

import csv
import io
import os
from dataclasses import dataclass, field

import numpy as np


@dataclass
class EETTable:
    eet: np.ndarray                     # (T_types, M_types) float32, seconds
    task_types: list[str] = field(default_factory=list)
    machine_types: list[str] = field(default_factory=list)

    def __post_init__(self):
        self.eet = np.asarray(self.eet, np.float32)
        t, m = self.eet.shape
        if not self.task_types:
            self.task_types = [f"t{i}" for i in range(t)]
        if not self.machine_types:
            self.machine_types = [f"m{j}" for j in range(m)]
        validate_eet(self.eet)

    @property
    def n_task_types(self) -> int:
        return self.eet.shape[0]

    @property
    def n_machine_types(self) -> int:
        return self.eet.shape[1]


def validate_eet(eet: np.ndarray) -> None:
    if eet.ndim != 2:
        raise ValueError(f"EET must be 2D (task_types x machine_types), "
                         f"got shape {eet.shape}")
    if not np.all(np.isfinite(eet)):
        raise ValueError("EET entries must be finite")
    if np.any(eet <= 0):
        raise ValueError("EET entries must be positive")


def load_eet_csv(path_or_text: str) -> EETTable:
    """Load an EET matrix from an E2C-style CSV file (or CSV text)."""
    if os.path.exists(path_or_text):
        with open(path_or_text, "r") as f:
            text = f.read()
    else:
        text = path_or_text
    rows = [r for r in csv.reader(io.StringIO(text)) if r and any(
        c.strip() for c in r)]
    header = [c.strip() for c in rows[0]]
    machine_types = header[1:]
    task_types, data = [], []
    for r in rows[1:]:
        task_types.append(r[0].strip())
        data.append([float(c) for c in r[1:]])
    return EETTable(np.asarray(data, np.float32), task_types, machine_types)


def save_eet_csv(table: EETTable, path: str) -> None:
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["task_type"] + table.machine_types)
        for name, row in zip(table.task_types, table.eet):
            w.writerow([name] + [f"{v:.6g}" for v in row])


def synth_eet(n_task_types: int, n_machine_types: int, *,
              task_var: float = 1.0, machine_var: float = 0.5,
              inconsistency: float = 0.2, base: float = 1.0,
              seed: int = 0, costs: np.ndarray | None = None) -> EETTable:
    """Synthesize an EET matrix with controlled heterogeneity.

    Uses the classic CVB (coefficient-of-variation based) style construction:
    a rank-1 consistent core ``task_cost[i] * machine_speed[j]`` perturbed by
    lognormal inconsistency noise.  ``inconsistency=0`` gives a *consistent*
    heterogeneous system (machine ordering identical for every task type);
    larger values mix the orderings (the interesting regime for MinMin etc.).
    ``costs`` (optional, (T,)) fixes each task type's cost, as a job
    type's runtime profile does; the machine factors and the noise are
    the draws the seed gives either way.
    """
    rng = np.random.default_rng(seed)
    task_cost = base * rng.lognormal(0.0, task_var, size=(n_task_types, 1))
    if costs is not None:
        task_cost = base * np.asarray(costs, np.float64).reshape(-1, 1)
    machine_slow = rng.lognormal(0.0, machine_var, size=(1, n_machine_types))
    noise = rng.lognormal(0.0, inconsistency,
                          size=(n_task_types, n_machine_types))
    return EETTable((task_cost * machine_slow * noise).astype(np.float32))


def homogeneous_eet(n_task_types: int, n_machine_types: int, *,
                    base: float = 1.0, task_var: float = 1.0,
                    seed: int = 0) -> EETTable:
    """All machine types identical — E2C's homogeneous-system mode."""
    rng = np.random.default_rng(seed)
    task_cost = base * rng.lognormal(0.0, task_var, size=(n_task_types, 1))
    return EETTable(np.repeat(task_cost, n_machine_types, 1).astype(np.float32))


def eet_from_roofline(rows: dict[str, dict[str, float]],
                      machine_specs: dict[str, dict[str, float]]) -> EETTable:
    """Derive an EET matrix from per-arch roofline terms + machine specs.

    ``rows[arch] = {"flops": HLO_FLOPs, "bytes": HLO_bytes}`` (from the
    compiled dry-run of one step) and ``machine_specs[mtype] =
    {"flops_per_s": ..., "hbm_bw": ...}``.  The EET entry is the roofline
    lower-bound time ``max(flops/peak, bytes/bw)`` — i.e. the simulator's
    heterogeneity model is calibrated from the *measured structure* of each
    architecture instead of hand-entered numbers.  (See
    benchmarks/eet_from_roofline.py for the end-to-end flow.)
    """
    task_types = sorted(rows)
    machine_types = sorted(machine_specs)
    eet = np.zeros((len(task_types), len(machine_types)), np.float32)
    for i, a in enumerate(task_types):
        for j, m in enumerate(machine_types):
            spec = machine_specs[m]
            t_c = rows[a]["flops"] / spec["flops_per_s"]
            t_m = rows[a]["bytes"] / spec["hbm_bw"]
            eet[i, j] = max(t_c, t_m)
    return EETTable(eet, task_types, machine_types)


def default_power(n_machine_types: int, *, idle: float = 10.0,
                  active_lo: float = 40.0, active_hi: float = 220.0,
                  seed: int = 0) -> np.ndarray:
    """(M_types, 2) [idle_W, active_W] — faster machines burn more power."""
    rng = np.random.default_rng(seed)
    active = np.sort(rng.uniform(active_lo, active_hi, n_machine_types))
    idle_w = np.full(n_machine_types, idle)
    return np.stack([idle_w, active], axis=1).astype(np.float32)
