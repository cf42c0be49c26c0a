"""Share of the we_rounds kernel's executed row-rounds that were a real
trial's rounds, in percent: a tile loops until its slowest row is done,
and padding rows run too.  Read from the program's process-wide
counters (``repro.tracing``) over every call of the run; a ratio, so
the warm-up call does not bias it."""


def read(ctx):
    try:
        from repro.tracing import counters
    except ImportError:              # a program without the counters
        return None
    c = counters()
    executed = c.get("we_rounds.row_rounds_executed", 0)
    if not executed:
        return None
    return 100.0 * c.get("we_rounds.row_rounds_useful", 0) / executed
