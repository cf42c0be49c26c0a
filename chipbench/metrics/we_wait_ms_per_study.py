"""Host milliseconds per call blocked reading the we_rounds results
back (waiting on the input layout, the kernel, the output layout and
the copy): the ``repro.we_rounds.wait`` spans."""
from chipbench import spans


def read(ctx):
    return spans.span_ms_per_call(ctx.reduced, ["repro.we_rounds.wait"])
