"""Seconds jax spent tracing and lowering to MLIR during set-up: the
``trace_s + lower_s`` of the ``compile_clock`` event with which the
program's telemetry log (``core/telemetry.py``) opens, the process's
compile-stage seconds before the window's log."""


def read(ctx):
    for r in ctx.get("spans") or []:
        if r.get("kind") == "event" and r.get("name") == "compile_clock" \
                and r.get("window") == "before":
            return r["trace_s"] + r["lower_s"]
    return None
