"""Share of the traced window in which a device ran no operation, on the
idlest device, in percent: 100 x (1 - busy / window)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_s"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - min(tr["busy_s"].values()) / tr["window_s"])
