"""Seconds of XLA compilation or persistent-cache load during set-up:
the sum of jax's ``backend_compile_duration`` events before the window."""


def read(ctx):
    return ctx.get("compile_s")
