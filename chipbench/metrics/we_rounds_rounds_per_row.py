"""Exchange rounds of an average trial in the we_rounds kernel: the
program's ``we_rounds.row_rounds_useful`` over ``we_rounds.rows``
(``repro.tracing``), over every call of the run; a ratio, so the
warm-up call does not bias it.  The final phase counts as one round.
None when the program keeps no such counters."""


def read(ctx):
    try:
        from repro.tracing import counters
    except ImportError:              # a program without the counters
        return None
    c = counters()
    rows = c.get("we_rounds.rows", 0)
    if not rows:
        return None
    return c.get("we_rounds.row_rounds_useful", 0) / rows
