"""One vmapped call over every replica: ``run_experiment(spec)``."""
import jax
import numpy as np

from repro.launch import experiment as X


def call(spec, traffic):
    res = X.run_experiment(spec)
    return jax.tree.map(np.asarray, res.metrics), None


def warm(spec, traffic):
    call(spec, traffic)
