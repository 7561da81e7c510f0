"""Device busy time in the traced window, summed over the devices, per
simulated task of the calls that lie wholly inside it, in microseconds."""


def read(ctx):
    tr = ctx.get("trace")
    tasks = ctx.get("tasks_traced", 0)
    if not tr or not tr["busy_s"] or not tasks:
        return None
    return 1e6 * sum(tr["busy_s"].values()) / tasks
