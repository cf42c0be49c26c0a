"""Milliseconds per call in which the device is idle and the host is in
no span of the program but the whole study's: host time that no layer
names (the harness's own bookkeeping among it)."""
from chipbench import spans


def read(ctx):
    return spans.untraced_ms_per_call(ctx.reduced)
