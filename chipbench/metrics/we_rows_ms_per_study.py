"""Host milliseconds per call assembling the we_rounds kernel's rows
(repeating the rate rows per trial, padding lanes and rows, stacking the
pair, the per-row flags): the ``repro.we_rounds.rows`` spans."""
from chipbench import spans


def read(ctx):
    return spans.span_ms_per_call(ctx.reduced, ["repro.we_rounds.rows"])
