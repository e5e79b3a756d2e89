"""AMService's profiler spans and its queue-wait counter, on the CPU.

A service runs under ``jax.profiler`` with a background driver: bulk load,
a few single-row appends, lookups answered through the driver.  The trace's
host planes must hold every ``am.`` span the service documents, nested as
documented, with one group's launch, completion and readback carrying the
same ``group``.  The queue-wait counter is checked exactly under a scripted
clock.
"""

import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.serve.am_service import AMService

WIDTH = 8
SPANS = ("am.append", "am.make_room", "am.write", "am.launch", "am.resolve",
         "am.readback", "am.driver.wait")


def _host_spans(path):
    """``(name, start_ns, end_ns, line, metadata)`` of every ``am.`` span."""
    out, line = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                name = ev.name.split("#", 1)[0]
                if name.startswith("am."):
                    out.append((name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, line,
                                dict(ev.stats)))
            line += 1
    return out


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    logdir = str(tmp_path_factory.mktemp("am-trace"))
    rng = np.random.default_rng(3)
    codes = rng.integers(0, 8, (40, WIDTH)).astype(np.int32)
    svc = AMService(time_fn=time.monotonic, max_batch=4, flush_after=0.002)
    svc.create_table("t", width=WIDTH, capacity=64, policy="reject",
                     backend="ref")
    svc.append("t", codes[:32])
    svc.start_driver()
    jax.profiler.start_trace(logdir)
    try:
        for i in range(32, 35):
            svc.append("t", codes[i])
        futs = [svc.submit("t", codes[i % 35], k=2) for i in range(10)]
        for f in futs:
            f.result(timeout=60.0)
        time.sleep(0.02)                  # the driver idles meanwhile
    finally:
        jax.profiler.stop_trace()
        svc.stop_driver()
    return _host_spans(glob.glob(f"{logdir}/**/*.xplane.pb",
                                 recursive=True)[0])


def _named(spans, name):
    return [sp for sp in spans if sp[0] == name]


def test_every_documented_span_appears(spans):
    assert {sp[0] for sp in spans} == set(SPANS)
    appends = _named(spans, "am.append")
    assert [sp[4] for sp in appends] == [{"table": "t", "rows": 1}] * 3
    assert [sp[4]["rows"] for sp in _named(spans, "am.make_room")] == [
        32, 33, 34]


def test_make_room_and_writes_nest_inside_append(spans):
    appends = _named(spans, "am.append")
    for name, per_append in (("am.make_room", 1), ("am.write", 2)):
        inner = _named(spans, name)
        assert len(inner) == per_append * len(appends)
        for _, s, e, line, _ in inner:
            assert sum(a[3] == line and a[1] <= s and e <= a[2]
                       for a in appends) == 1
    assert sorted(sp[4]["slab"] for sp in _named(spans, "am.write")) == [
        "codes"] * 3 + ["meta"] * 3


def test_one_groups_stages_share_its_group(spans):
    launches = _named(spans, "am.launch")
    assert launches
    assert sum(sp[4]["lookups"] for sp in launches) == 10
    for _, _, _, _, meta in launches:
        b = meta["bucket"]                # a power of two
        assert b >= 1 and b & (b - 1) == 0
    groups = sorted(sp[4]["group"] for sp in launches)
    assert groups == list(range(groups[0], groups[0] + len(groups)))
    for g in groups:
        resolve = [sp for sp in _named(spans, "am.resolve")
                   if sp[4]["group"] == g]
        readback = [sp for sp in _named(spans, "am.readback")
                    if sp[4]["group"] == g]
        assert len(resolve) == 1 and len(readback) == 1
        (_, rs, re_, rl, _), (_, bs, be, bl, _) = resolve[0], readback[0]
        assert rl == bl and rs <= bs and be <= re_


def test_driver_waits_on_its_own_thread(spans):
    waits = _named(spans, "am.driver.wait")
    assert len({sp[3] for sp in waits}) == 1
    assert waits[0][3] != _named(spans, "am.append")[0][3]


@pytest.mark.parametrize("repeats", [False, True],
                         ids=["distinct", "deduplicated"])
def test_queue_wait_is_launch_minus_submit(repeats):
    """Submits at clock 0, 1 and 2, launched by a flush at 5: the lookups
    waited 5 + 4 + 3 clock units, repeats of one query included."""
    clock = [0.0]
    svc = AMService(time_fn=lambda: clock[0], max_batch=64)
    svc.create_table("t", width=WIDTH, capacity=16, backend="ref")
    codes = np.zeros((3, WIDTH), np.int32) + np.arange(3)[:, None]
    svc.append("t", codes)
    for at in (0.0, 1.0, 2.0):
        clock[0] = at
        svc.submit("t", codes[0 if repeats else int(at)])
    assert svc.stats()["queue_wait_s"] == 0.0    # nothing launched yet
    clock[0] = 5.0
    svc.flush()
    s = svc.stats()
    assert s["queue_wait_s"] == 12.0
    assert svc.dispatched == 3
    assert s["dedup_hits"] == (2 if repeats else 0)
    assert not {"queue_wait_p50", "queue_wait_p99"} & set(s)

