"""insert_ms.tput: median host wall time of the benchmark's span around each
insert's ``append`` in the window."""

import statistics


def read(ctx):
    ms = ctx.log.insert_ms
    return statistics.median(ms) if ms else None
