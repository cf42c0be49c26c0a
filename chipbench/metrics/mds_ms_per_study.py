"""Milliseconds per call in the MDS scheme (the code-length sweep on
Gamma rows, its sort and selection, the winners' top-up and reports):
the ``repro.scheme.mds`` spans."""
from chipbench import spans


def read(ctx):
    return spans.span_ms_per_call(ctx.reduced, ["repro.scheme.mds"])
