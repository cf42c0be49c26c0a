"""Seconds from process start to the start of the window: interpreter,
jax and TPU init, the compile cache and the warm-up calls."""


def read(ctx):
    return ctx.setup_s
