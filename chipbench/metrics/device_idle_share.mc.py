"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the devices that ran anything."""
from chipbench import trace


def read(ctx):
    share = trace.idle_share(ctx.reduced)
    return None if share is None else 100.0 * share
