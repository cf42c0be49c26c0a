"""Host milliseconds per call in the schemes that run on numpy alone
(``fixed``, ``het_mds``, ``hedged``): their ``repro.scheme.*`` spans."""
from chipbench import spans

NAMES = ["repro.scheme.fixed", "repro.scheme.het_mds", "repro.scheme.hedged"]


def read(ctx):
    return spans.span_ms_per_call(ctx.reduced, NAMES)
