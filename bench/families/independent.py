"""Braun's independent tasks: a fleet, arrivals and a scenario grid.

The program's scenario mode (``launch/experiment.py``): every replica is
one cell of a mixed-radix grid over fail rate, DVFS state, policy and
arrival process (``inputs.py``), its tasks independent of each other.
Its draws are ``inputs.draw``, its reference ``reference.simulate``;
both import only numpy.
"""
from __future__ import annotations

import numpy as np

from bench.inputs import draw  # noqa: F401
from bench.reference import (COUNT_COLUMNS, VALUE_COLUMNS,  # noqa: F401
                             simulate)


def axes(config: dict, traffic: dict) -> dict:
    """The cell's grid axes: the config's scenario, narrowed by traffic."""
    scen = dict(config["scenario"], **traffic.get("scenario", {}))
    return {"fail_rates": list(scen["fail_rates"]),
            "dvfs_states": list(scen["dvfs_states"]),
            "spot_frac": float(scen["spot_frac"]),
            "mttr": float(scen["mttr"]),
            "n_intervals": int(scen["n_intervals"]),
            "policies": list(traffic["policies"]),
            "arrivals": list(traffic["arrivals"])}


def make_spec(config: dict, traffic: dict, seed: int):
    """The ``ExperimentSpec`` one call of the cell runs, for ``seed``."""
    from repro.launch import experiment as X
    ax = axes(config, traffic)
    return X.ExperimentSpec(
        n_replicas=traffic["replicas"],
        fleet=X.FleetAxis(config["n_machines"], config["n_machine_types"]),
        workload=X.WorkloadAxis(config["n_tasks"],
                                n_task_types=config["n_task_types"],
                                rate=config["rate"],
                                arrivals=tuple(ax["arrivals"]),
                                streaming=traffic.get("streaming")),
        scenario=X.ScenarioAxis(fail_rates=tuple(ax["fail_rates"]),
                                dvfs_states=tuple(ax["dvfs_states"]),
                                spot_frac=ax["spot_frac"],
                                mttr=ax["mttr"],
                                n_intervals=ax["n_intervals"]),
        policy=X.PolicyAxis(tuple(ax["policies"])),
        seed=seed)


def replica_policies(axes: dict, n_replicas: int) -> np.ndarray:
    """(R,) index into ``axes["policies"]`` of every replica of a call."""
    r = np.arange(n_replicas)
    n_fd = len(axes["fail_rates"]) * len(axes["dvfs_states"])
    return (r // n_fd) % len(axes["policies"])
