"""prep_device_ms.tail: median device time per dispatch of every operation
in the dispatch other than the top-k kernel (expansion, casts, padding, the
meta touch), from the trace."""

import statistics

from onchip import tracereduce

KERNEL = "cam_search_topk"
DISPATCH = "dispatch"


def read(ctx):
    if ctx.trace is None:
        return None
    prep = tracereduce.dispatch_prep(ctx.trace, DISPATCH, KERNEL)
    if not prep:
        return None
    return statistics.median(prep) * 1e3
