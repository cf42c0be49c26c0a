"""Executables built (compiled, or read from the persistent cache)
between the window's start and its end, counted through jax.monitoring."""


def read(ctx):
    return float(ctx.compiles_in_window)
