"""Chunked Monte-Carlo grid: ``run_experiment(spec, chunk=C)``.

The donated, device-reduced path a study runs: chunks of C replicas,
normalize of chunk c+1 on the host while chunk c runs, every chunk
folded into the on-device ``SweepAgg``.  ``keep_replicas`` brings the
per-replica rows back as well, so the check can hold both the rows and
their fold to the reference.
"""
from repro.launch import experiment as X


def call(spec, traffic):
    res = X.run_experiment(spec, chunk=traffic["chunk"], keep_replicas=True)
    return res.metrics, res.agg


def warm(spec, traffic):
    """One call of a single chunk compiles the chunk step at its shapes."""
    call(spec.with_(n_replicas=traffic["chunk"]), traffic)
