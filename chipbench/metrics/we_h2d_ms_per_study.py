"""Host milliseconds per call the runtime spends laying out the
we_rounds kernel's inputs for the transfer: its ``XlaLinearize`` events,
on any host thread, that start inside a ``repro.we_rounds`` span."""
from chipbench import spans


def read(ctx):
    return spans.linearize_ms_per_call(ctx.reduced)
