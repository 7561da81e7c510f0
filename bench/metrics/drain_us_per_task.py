"""Device time in the scheduler's drain, per simulated task of the
traced calls, in microseconds: the exclusive time of the ops whose
phase is the engine's ``drain`` scope (``bench/phases.py``), inside the
traced window, summed over the devices."""
from bench import phases as PH


def read(ctx):
    tr = PH.from_ctx(ctx)
    tasks = ctx.get("tasks_traced", 0)
    if not tr or not tasks or not PH.scoped(tr):
        return None
    return 1e6 * PH.split(tr, *tr["window_ns"]).get("drain", 0.0) / tasks
