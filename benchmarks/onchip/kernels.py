"""The top-k kernel's calls in a trace: their times and their roofline.

The kernel is found by its stable name, ``cam_search_topk``.  Its least time
per call comes from ``roofline.topk_least_time`` with the configuration's
own rows (the rows loaded), cells and bits, and the call's query rows.
"""

from __future__ import annotations

import statistics

from onchip import roofline, tracereduce

KERNEL = "cam_search_topk"


def calls(ctx) -> list:
    if ctx.trace is None:
        return []
    return tracereduce.kernel_calls(ctx.trace, KERNEL)


def median_call_ms(ctx) -> float | None:
    c = calls(ctx)
    return statistics.median(op[2] for op in c) / 1e6 if c else None


def least_times(ctx) -> list:
    """``(least seconds, binding term)`` of each call in the window."""
    cfg = ctx.cfg
    rows = cfg.get("rows", cfg.get("recordcount"))
    k = ctx.cell.traffic["k"]
    return [roofline.topk_least_time(tracereduce.call_rows(op), rows,
                                     cfg["width"], cfg["bits"], k, ctx.peak)
            for op in calls(ctx)]


def roofline_pct(ctx) -> float | None:
    c = calls(ctx)
    if not c:
        return None
    least = sum(t for t, _ in least_times(ctx))
    return 100.0 * least / (sum(op[2] for op in c) / 1e9)
