"""Device milliseconds of the we_rounds kernel per call: the summed
durations of its events in the trace over the calls traced.  The kernel
is the only Mosaic custom call these cells launch; the trace names it
by its HLO line, ``... custom_call_target="tpu_custom_call" ...``."""
from chipbench import trace

NEEDLE = "tpu_custom_call"


def read(ctx):
    ns = trace.kernel_ns(ctx.reduced, NEEDLE)
    calls = len(ctx.reduced["calls"])
    return None if ns is None or not calls else ns / calls / 1e6
