"""Scheme-trials returned by the window's calls per second of the window
(host clock)."""
from chipbench import bench


def read(ctx):
    return bench.work_rate(ctx.calls)
