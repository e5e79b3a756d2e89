"""queue_wait_ms.tail: mean time a lookup waited in the service's queue,
from its submit to the launch of its group, across the window: the increase
of ``stats()["queue_wait_s"]`` over the increase of ``dispatched`` (service
counters).  ``None`` where the service keeps no such counter."""


def read(ctx):
    if "queue_wait_s" not in ctx.before:
        return None
    lookups = ctx.after["dispatched"] - ctx.before["dispatched"]
    if lookups <= 0:
        return None
    waited = ctx.after["queue_wait_s"] - ctx.before["queue_wait_s"]
    return 1e3 * waited / lookups
