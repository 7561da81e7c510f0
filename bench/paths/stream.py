"""Bounded-memory streams: ``run_experiment(spec)`` with
``WorkloadAxis(streaming=W)``, every replica through a W-slot window
(``core/streaming.py``: retire, refill, compact).  The call is the
monolithic one; the spec alone routes it to the streaming engine."""
from bench.paths.monolithic import call  # noqa: F401
from repro.launch import experiment as X


def warm(spec, traffic):
    """Compile the stream sweep at the window's shapes without running
    it: a call lasts tens of seconds, and the jitted sweep keeps the
    executable, so the window's first call neither traces nor compiles."""
    reps = X.normalize(spec)
    stream = X.to_streams(reps, spec.stream_chunk)
    X.compile_experiment(spec).lower(
        stream, reps.mtype, reps.tables.eet, reps.tables.power,
        reps.policy_ids, reps.dynamics, None).compile()
