"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain form (``Trace``) that can be saved as JSON and reduced without JAX:

* ``ops`` — device operations of the chip (TPU:0 unless told otherwise), each
  ``[name, start_ns, dur_ns]``, from the device plane's ``XLA Ops`` line;
  the name is the operation's HLO text (``%cam_search_topk.1 = (s32[64,128]
  ...) custom-call(...)``);
* ``modules`` — compiled programs run on the device, ``[name, start_ns,
  dur_ns]``, from its ``XLA Modules`` line;
* ``spans`` — the benchmark's own host spans (names starting ``bench.``);
* ``window`` — ``[start_ns, end_ns]`` of the ``bench.window`` span.

The reductions: the union of device busy intervals and the idle share of
the window; each kernel call's device time, found by its stable name; the
device time of the rest of each dispatch; the longest device operations;
and the idle gaps, attributed to the host span the benchmark was in.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import os
import re

#: Host spans the benchmark records, and the one that marks the window.
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: list
    modules: list
    spans: list
    window: list

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(dataclasses.asdict(self), f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls(**json.load(f))


def find_xplane(logdir: str) -> str:
    found = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
             for f in fs if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {found}")
    return found[0]


def extract(path: str, device: str = "/device:TPU:0") -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, spans, window = [], [], [], None
    planes = {p.name: p for p in data.planes}
    if device not in planes:
        raise RuntimeError(f"no plane {device!r} in the trace: "
                           f"{sorted(planes)}")
    lines = {line.name: line for line in planes[device].lines}
    for need in ("XLA Ops", "XLA Modules"):
        if need not in lines:
            raise RuntimeError(f"no line {need!r} on {device}: "
                               f"{sorted(lines)}")
    for ev in lines["XLA Ops"].events:
        ops.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    for ev in lines["XLA Modules"].events:
        modules.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    s = [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    if ev.name == WINDOW_SPAN:
                        window = [s[1], s[1] + s[2]]
                    else:
                        spans.append(s)
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    return Trace(ops=ops, modules=modules, spans=spans, window=window)


def union(intervals) -> list:
    """Merged ``[start, end]`` intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def busy(trace: Trace) -> list:
    """Intervals in the window in which some operation ran on the device."""
    lo, hi = trace.window
    return _clip(union([[s, s + d] for _, s, d in trace.ops]), lo, hi)


def busy_s(trace: Trace) -> float:
    return sum(e - s for s, e in busy(trace)) / 1e9


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / window_s(trace)


def in_window(trace: Trace, items) -> list:
    lo, hi = trace.window
    return [x for x in items if lo <= x[1] < hi]


def kernel_calls(trace: Trace, kernel: str) -> list:
    """The window's calls of the kernel whose stable name is ``kernel``."""
    return [op for op in in_window(trace, trace.ops) if kernel in op[0]]


def call_rows(op) -> int:
    """Query rows of a kernel call: the leading dimension of its first
    result, as the operation's HLO text gives it (``s32[64,128]``)."""
    m = re.search(r"[sfu]\d+\[(\d+),", op[0])
    if m is None:
        raise ValueError(f"no result shape in {op[0]!r}")
    return int(m.group(1))


def dispatch_prep(trace: Trace, module: str, kernel: str) -> list:
    """For each run of a program whose name holds ``module``: the device
    seconds of its operations other than ``kernel``."""
    runs = [m for m in in_window(trace, trace.modules) if module in m[0]]
    ops = sorted(trace.ops, key=lambda op: op[1])
    starts = [op[1] for op in ops]
    out = []
    for _, s, d in runs:
        i = bisect.bisect_left(starts, s)
        t = 0
        while i < len(ops) and ops[i][1] < s + d:
            if kernel not in ops[i][0]:
                t += ops[i][2]
            i += 1
        out.append(t / 1e9)
    return out


def short_name(name: str) -> str:
    """``%cam_search_topk.1 = (s32[64,128]{...}, ...) custom-call(...)`` ->
    ``cam_search_topk.1 (s32[64,128], ...) custom-call``: the operation,
    its result type without layouts, and its kind."""
    m = re.match(r"%?(\S+) = (.*?) ([\w-]+)\(", name)
    if m is None:
        return name[:120]
    result = re.sub(r"\{[^{}]*\}", "", m.group(2))
    return f"{m.group(1)} {result} {m.group(3)}"[:120]


def top_ops(trace: Trace, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the window's device operations by total
    time, heaviest first."""
    total: dict[str, int] = {}
    for name, _, d in in_window(trace, trace.ops):
        key = short_name(name)
        total[key] = total.get(key, 0) + d
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / 1e9] for name, t in top]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """``[[span, seconds], ...]``: the window's idle device time, split by
    the benchmark's host span that covered it (``no_span`` where none)."""
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in busy(trace):
        if s > t:
            gaps.append([t, s])
        t = max(t, e)
    if t < hi:
        gaps.append([t, hi])
    spans = union_by_name(trace.spans)
    total: dict[str, float] = {}
    for gs, ge in gaps:
        covered = 0
        for name, (starts, ends) in spans.items():
            i = bisect.bisect_right(ends, gs)
            c = 0
            while i < len(starts) and starts[i] < ge:
                c += min(ends[i], ge) - max(starts[i], gs)
                i += 1
            if c:
                total[name] = total.get(name, 0) + c
                covered += c
        rest = (ge - gs) - covered
        if rest > 0:
            total["no_span"] = total.get("no_span", 0) + rest
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / 1e9] for name, t in top]


def union_by_name(spans) -> dict:
    """Each span name's merged intervals, as sorted starts and ends."""
    by: dict[str, list] = {}
    for name, s, d in spans:
        by.setdefault(name, []).append([s, s + d])
    out = {}
    for name, ivs in by.items():
        merged = union(ivs)
        out[name] = ([s for s, _ in merged], [e for _, e in merged])
    return out
