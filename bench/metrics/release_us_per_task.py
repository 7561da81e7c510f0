"""Device time in workflow precedence, per simulated task of the traced
calls, in microseconds: the exclusive time of the ops whose phase is the
engine's ``release`` scope (``bench/phases.py``: the release phase and
the precedence part of the next-event search), inside the traced window,
summed over the devices."""
from bench import phases as PH


def read(ctx):
    tr = PH.from_ctx(ctx)
    tasks = ctx.get("tasks_traced", 0)
    if not tr or not tasks or not PH.scoped(tr):
        return None
    return 1e6 * PH.split(tr, *tr["window_ns"]).get("release", 0.0) / tasks
