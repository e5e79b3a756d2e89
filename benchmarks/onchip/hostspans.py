#!/usr/bin/env python3
"""Host spans of a profiler trace by thread, and the idle time they name.

``tracereduce.extract`` keeps the benchmark's own ``bench.`` spans, without
the thread each ran on.  ``extract`` here reads the same ``.xplane.pb`` for
the program's ``am.`` spans as well (``AMService``'s stages; the module
docstring of ``repro.serve.am_service`` lists them) and keeps each span's
host thread, its plane line.  From them:

* ``idle_gaps`` gives each idle device instant of the window to exactly
  one name, the first of these open at that instant: the client thread's
  innermost ``am.`` span (the client is the thread that records the
  ``bench.`` spans); the driver thread's innermost ``am.`` span other than
  ``am.driver.wait``; ``am.driver.wait``; the client's innermost ``bench.``
  span; else ``no_span``.  The seconds add up to the idle time.
* ``launch_host_ms`` — median host time of the window's ``am.launch``
  spans (dedup, padding, the dispatch's enqueue);
* ``make_room_ms`` — median of the window's ``am.make_room`` spans (meta
  readback and eviction before an insert);
* ``slab_write_ms`` — median, per ``am.append``, of the ``am.write`` spans
  inside it.

A run keeps its window's trace with ``run.py --trace 1 --keep-trace <dir>``;

  python benchmarks/onchip/hostspans.py <dir>/trace.xplane.pb

prints these reductions as one JSON object.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import os
import statistics
import sys

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from onchip import tracereduce  # noqa: E402

PROGRAM_PREFIX = "am."
BENCH_PREFIX = tracereduce.SPAN_PREFIX
DRIVER_WAIT = "am.driver.wait"
PREFIXES = (PROGRAM_PREFIX, BENCH_PREFIX)


@dataclasses.dataclass
class HostSpans:
    """``spans``: ``[name, start_ns, dur_ns, line, args]`` for every
    ``am.`` and ``bench.`` span but the window, ``line`` numbering the host
    planes' lines in trace order and ``args`` the span's metadata;
    ``window``: ``[start_ns, end_ns]`` of ``bench.window``."""

    spans: list
    window: list

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(dataclasses.asdict(self), f)

    @classmethod
    def load(cls, path: str) -> "HostSpans":
        with gzip.open(path, "rt") as f:
            return cls(**json.load(f))


def extract(path: str) -> HostSpans:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, window, line = [], None, 0
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                # without the "#key=value#" metadata a profiler may append
                name = ev.name.split("#", 1)[0]
                if not name.startswith(PREFIXES):
                    continue
                s, d = int(ev.start_ns), int(ev.duration_ns)
                if name == tracereduce.WINDOW_SPAN:
                    window = [s, s + d]
                else:
                    spans.append([name, s, d, line, dict(ev.stats)])
            line += 1
    if window is None:
        raise RuntimeError(f"no {tracereduce.WINDOW_SPAN!r} span in the "
                           f"trace")
    return HostSpans(spans=spans, window=window)


def _busiest_line(spans, keep) -> int | None:
    count: dict[int, int] = {}
    for name, _, _, line, _ in spans:
        if keep(name):
            count[line] = count.get(line, 0) + 1
    return max(count, key=count.get) if count else None


def threads(hs: HostSpans) -> tuple[int | None, int | None]:
    """``(client, driver)`` lines: the one that records most ``bench.``
    spans, and the one that records most ``am.driver.wait``."""
    return (_busiest_line(hs.spans, lambda n: n.startswith(BENCH_PREFIX)),
            _busiest_line(hs.spans, lambda n: n == DRIVER_WAIT))


def innermost(spans) -> list:
    """``[start, end, name]`` segments, in order and disjoint, each naming
    the innermost of the (nested) ``[name, start, dur]`` spans open there."""
    out, stack, t = [], [], 0
    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if t < end:
                out.append([t, end, top])
                t = end
        if stack and t < s:
            out.append([t, s, stack[-1][0]])
        stack.append([name, s + d])
        t = s
    while stack:
        top, end = stack.pop()
        if t < end:
            out.append([t, end, top])
            t = end
    return out


def _take(free, segs, total) -> list:
    """Credit each part of the ``free`` intervals that a segment covers to
    the segment's name in ``total``; return the parts none covers."""
    out, j = [], 0
    for s, e in free:
        t = s
        while j < len(segs) and segs[j][1] <= t:
            j += 1
        while j < len(segs) and segs[j][0] < e:
            a, b, name = segs[j]
            if a > t:
                out.append([t, a])
            lo, hi = max(a, t), min(b, e)
            total[name] = total.get(name, 0) + hi - lo
            t = hi
            if b > e:
                break
            j += 1
        if t < e:
            out.append([t, e])
    return out


def idle_gaps(trace: tracereduce.Trace, hs: HostSpans, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the window's idle device time, each
    instant given to one name by the ranks in the module docstring."""
    lo, hi = trace.window
    free, t = [], lo
    for s, e in tracereduce.busy(trace):
        if s > t:
            free.append([t, s])
        t = max(t, e)
    if t < hi:
        free.append([t, hi])
    client, driver = threads(hs)

    def on(line, keep):
        return innermost([[name, s, d] for name, s, d, ln, _ in hs.spans
                          if ln == line and keep(name)])

    ranks = [
        on(client, lambda x: x.startswith(PROGRAM_PREFIX)),
        on(driver, lambda x: x.startswith(PROGRAM_PREFIX)
           and x != DRIVER_WAIT),
        on(driver, lambda x: x == DRIVER_WAIT),
        on(client, lambda x: x.startswith(BENCH_PREFIX)),
    ]
    total: dict[str, int] = {}
    for segs in ranks:
        free = _take(free, segs, total)
    rest = sum(e - s for s, e in free)
    if rest:
        total["no_span"] = rest
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / 1e9] for name, t in top]


def in_window(hs: HostSpans, name: str) -> list:
    lo, hi = hs.window
    return [sp for sp in hs.spans if sp[0] == name and lo <= sp[1] < hi]


def _median_ms(durations) -> float | None:
    return statistics.median(durations) / 1e6 if durations else None


def launch_host_ms(hs: HostSpans) -> float | None:
    return _median_ms([sp[2] for sp in in_window(hs, "am.launch")])


def make_room_ms(hs: HostSpans) -> float | None:
    return _median_ms([sp[2] for sp in in_window(hs, "am.make_room")])


def slab_write_ms(hs: HostSpans) -> float | None:
    writes = [sp for sp in hs.spans if sp[0] == "am.write"]
    per_append = []
    for _, s, d, line, _ in in_window(hs, "am.append"):
        per_append.append(sum(w[2] for w in writes
                              if w[3] == line and s <= w[1] < s + d))
    return _median_ms(per_append)


def reduce(path: str) -> dict:
    """The reductions of one raw trace."""
    hs = extract(path)
    return {"launch_host_ms": launch_host_ms(hs),
            "make_room_ms": make_room_ms(hs),
            "slab_write_ms": slab_write_ms(hs),
            "idle_gaps": idle_gaps(tracereduce.extract(path), hs)}


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1])))
