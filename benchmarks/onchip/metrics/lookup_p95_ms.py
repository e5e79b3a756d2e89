"""lookup_p95_ms: 95th percentile of every lookup due in the window, each
timed from when it was due to its answer; refused or unanswered lookups
count as missing (they wait out the grace period)."""

import statistics


def read(ctx):
    lat = ctx.read_latency_ms()
    if len(lat) < 20:
        return None
    return statistics.quantiles(lat.tolist(), n=20, method="inclusive")[-1]
