"""device_idle_pct.tput: the share of the window in which no operation ran
on the device, from the trace."""

from onchip import tracereduce


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * tracereduce.idle_share(ctx.trace)
