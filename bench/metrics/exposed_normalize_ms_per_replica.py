"""Normalize time the device sat idle through, per replica normalized,
in milliseconds: on the idlest device, the idle time inside the union of
the window's ``e2c.normalize`` and ``e2c.chunk_normalize`` annotations,
over the ``n_replicas`` those spans normalized (``bench/phases.py``).
A normalize that hides behind device work reads 0."""
from bench import phases as PH


def read(ctx):
    tr = PH.from_ctx(ctx)
    got = PH.exposed(tr, *tr["window_ns"]) if tr else None
    if not got or not got[1]:
        return None
    idle_ns, replicas = got
    return idle_ns / 1e6 / replicas
