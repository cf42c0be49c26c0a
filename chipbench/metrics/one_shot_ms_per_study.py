"""Device milliseconds per call of the one-shot program, ``jit_one_shot``
(the draws and covers of ``fixed``, ``het_mds`` and ``hedged``): the
summed durations of its runs on every device plane of the reduced
trace, over the calls traced.  None when the trace holds no such run,
as a program without the device one-shot path gives."""

PROGRAM = "jit_one_shot"


def read(ctx):
    red = ctx.reduced
    ns = [d for evs in red["devices"].values() for name, _, d in evs
          if name.split("(", 1)[0] == PROGRAM]
    calls = len(red["calls"])
    return sum(ns) / calls / 1e6 if ns and calls else None
