"""lookups_per_dispatch.tail: lookups the service front dispatched over the
dispatch groups it launched, across the window (service counters)."""


def read(ctx):
    groups = ctx.after["flushes"] - ctx.before["flushes"]
    if groups <= 0:
        return None
    return (ctx.after["dispatched"] - ctx.before["dispatched"]) / groups
