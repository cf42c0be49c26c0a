"""Mean over the traced calls of the call's span minus the device-busy
time inside it, in milliseconds: host draws, padding and reports."""
from chipbench import trace


def read(ctx):
    ns = trace.host_ns_per_call(ctx.reduced)
    return None if ns is None else ns / 1e6
