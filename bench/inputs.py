"""The benchmark's own copy of how a replica's inputs follow from a seed,
for the ``independent`` family (``families/independent.py``).

The program draws every replica of a call from the call's seed
(``launch/experiment.py``: ``normalize``).  The reference must take
nothing the program made, so this module draws the same replicas again,
from the same seed, by the documented construction: the CVB-style EET
matrix, per-type power, arrivals, the failure trace and DVFS state, the
spot draw, per-task noise and the machine-type assignment.  Only numpy.

A cell's grid is mixed-radix over the replica index ``r``: fail rate
``r % n_f``, DVFS state ``(r // n_f) % n_d``, policy
``(r // (n_f n_d)) % n_p``, arrival process ``(r // (n_f n_d n_p)) % n_a``.
"""
from __future__ import annotations

import numpy as np

DVFS_STATES = {"nominal": (1.00, 1.00), "balanced": (0.80, 0.55),
               "powersave": (0.60, 0.30), "turbo": (1.20, 1.60)}
INCONSISTENCY = 0.3
SLACK = 4.0


def grid_cell(axes: dict, r: int) -> dict:
    """Replica ``r``'s fail rate, DVFS state, policy and arrival process."""
    n_f, n_d = len(axes["fail_rates"]), len(axes["dvfs_states"])
    n_p = len(axes["policies"])
    return {"fail_rate": axes["fail_rates"][r % n_f],
            "dvfs": axes["dvfs_states"][(r // n_f) % n_d],
            "policy": axes["policies"][(r // (n_f * n_d)) % n_p],
            "arrival": axes["arrivals"][(r // (n_f * n_d * n_p))
                                        % len(axes["arrivals"])]}


def synth_eet(n_task_types: int, n_machine_types: int, seed: int):
    """CVB-style EET: lognormal task cost x machine slowness x noise."""
    rng = np.random.default_rng(seed)
    task_cost = rng.lognormal(0.0, 1.0, size=(n_task_types, 1))
    machine_slow = rng.lognormal(0.0, 0.5, size=(1, n_machine_types))
    noise = rng.lognormal(0.0, INCONSISTENCY,
                          size=(n_task_types, n_machine_types))
    return (task_cost * machine_slow * noise).astype(np.float32)


def _sorted(arrival, type_id, deadline):
    arrival = np.asarray(arrival, np.float32)
    order = np.argsort(arrival, kind="stable")
    return (arrival[order], np.asarray(type_id, np.int32)[order],
            np.asarray(deadline, np.float32)[order])


def poisson(n: int, rate: float, n_types: int, mean_eet, seed: int):
    rng = np.random.default_rng(seed)
    arrival = np.cumsum(rng.exponential(1.0 / rate, size=n)
                        ).astype(np.float32)
    type_id = rng.choice(n_types, size=n, p=np.full(n_types, 1.0 / n_types))
    jitter = rng.lognormal(0.0, 0.5, size=n)
    deadline = arrival + SLACK * jitter * mean_eet[type_id]
    return _sorted(arrival, type_id, deadline.astype(np.float32))


def bursty(n: int, rate: float, n_types: int, mean_eet, seed: int):
    """Markov-modulated Poisson: a tenth of the gaps at eight times the rate."""
    rng = np.random.default_rng(seed)
    rates = np.where(rng.random(n) < 0.1, rate * 8.0, rate)
    arrival = np.cumsum(rng.exponential(1.0 / rates)).astype(np.float32)
    type_id = rng.integers(0, n_types, n)
    deadline = arrival + SLACK * mean_eet[type_id]
    return _sorted(arrival, type_id, deadline.astype(np.float32))


ARRIVALS = {"poisson": poisson, "bursty": bursty}


def failure_trace(n_machines: int, n_intervals: int, fail_rate: float,
                  mttr: float, seed: int):
    """Alternating Exp(1/fail_rate) up and Exp(mttr) down periods."""
    rng = np.random.default_rng(seed)
    start = np.full((n_machines, n_intervals), np.inf, np.float32)
    end = np.full((n_machines, n_intervals), np.inf, np.float32)
    for m in range(n_machines):
        t = 0.0
        for k in range(n_intervals):
            t += rng.exponential(1.0 / fail_rate)
            d = rng.exponential(mttr)
            start[m, k] = t
            end[m, k] = t + d
            t += d
    return start, end


def draw(config: dict, axes: dict, seed: int, r: int) -> tuple[dict, str]:
    """-> (replica ``r``'s inputs as float32/int arrays, its policy)."""
    n, n_m = config["n_tasks"], config["n_machines"]
    n_tt, n_mt = config["n_task_types"], config["n_machine_types"]
    cell = grid_cell(axes, r)
    rng = np.random.default_rng([seed, r])
    eet = synth_eet(n_tt, n_mt, seed + r)
    power = np.stack([rng.uniform(20, 60, n_mt), rng.uniform(80, 300, n_mt)],
                     axis=1).astype(np.float32)
    arrival, type_id, deadline = ARRIVALS[cell["arrival"]](
        n, config["rate"], n_tt, eet.mean(1), seed + 7919 * r)
    if cell["fail_rate"] > 0.0:
        down_start, down_end = failure_trace(
            n_m, axes["n_intervals"], cell["fail_rate"], axes["mttr"],
            seed + 31 * r)
    else:
        down_start = np.full((n_m, axes["n_intervals"]), np.inf, np.float32)
        down_end = down_start.copy()
    kill = rng.random() < axes["spot_frac"]
    speed, power_scale = DVFS_STATES[cell["dvfs"]]
    noise = rng.lognormal(0.0, 0.1, n).astype(np.float32)
    mtype = rng.integers(0, n_mt, n_m)
    return {"arrival": arrival, "type_id": type_id, "deadline": deadline,
            "eet": eet, "power": power, "mtype": mtype, "noise": noise,
            "speed": np.full(n_m, speed, np.float32),
            "power_scale": np.full(n_m, power_scale, np.float32),
            "down_start": down_start, "down_end": down_end,
            "kill": np.full(n_m, kill, bool)}, cell["policy"]
