"""Host spans by thread: the idle attribution and the span reductions.

The five ranks of ``hostspans.idle_gaps`` are checked on a hand-built
two-thread trace; on ``testdata/sift_steady_2s.*``, recorded before the
program had spans, the rule gives what ``tracereduce.idle_gaps`` gives.
``testdata/ycsb_latest_2s.*`` is a 2-second ``--trace 1`` window of
``ycsb_1m.d_latest`` on a TPU v5 lite with the program's spans: the raw
``.xplane.pb``, gzipped, its ``tracereduce`` form and its ``hostspans``
form.
"""

import gzip
import os
import shutil
import types

import pytest

from onchip import catalog, hostspans, tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "testdata")
CLIENT, DRIVER = 0, 1

#: Two threads over a window of 100 ns whose device idles from 10 to 90.
SPANS = [
    ["bench.append", 20, 20, CLIENT, {}],
    ["am.append", 22, 16, CLIENT, {}],
    ["am.make_room", 24, 6, CLIENT, {}],
    ["bench.submit", 50, 20, CLIENT, {}],
    ["am.launch", 15, 20, DRIVER, {}],
    ["am.driver.wait", 40, 20, DRIVER, {}],
    ["am.resolve", 60, 20, DRIVER, {}],
    ["am.readback", 62, 8, DRIVER, {}],
]


def _trace(idle):
    """A window of 100 ns whose device is busy but for ``idle``."""
    lo, hi = idle
    return tracereduce.Trace(ops=[["%a = s32[2] add(x)", 0, lo],
                                  ["%b = s32[2] add(x)", hi, 100 - hi]],
                             modules=[], spans=[], window=[0, 100])


def _gaps(idle):
    hs = hostspans.HostSpans(spans=SPANS, window=[0, 100])
    return {k: round(v * 1e9) for k, v in
            hostspans.idle_gaps(_trace(idle), hs)}


@pytest.mark.parametrize("idle, name", [
    # the client's innermost am. span, over the driver's am.launch and the
    # client's bench.append
    ((22, 24), "am.append"),
    # the driver's innermost am. span, over the client's bench.append
    ((20, 22), "am.launch"),
    # the driver's wait, over the client's bench.submit
    ((50, 60), "am.driver.wait"),
    # the client's bench. span, with nothing of the program's open
    ((38, 40), "bench.append"),
    ((80, 90), "no_span"),
], ids=["client_am", "driver_am", "driver_wait", "client_bench", "none"])
def test_each_rank_takes_its_idle_time(idle, name):
    assert _gaps(idle) == {name: idle[1] - idle[0]}


def test_every_idle_instant_is_given_once():
    gaps = _gaps((10, 90))
    assert gaps == {"no_span": 15, "am.driver.wait": 20, "am.resolve": 12,
                    "am.append": 10, "am.readback": 8, "am.launch": 7,
                    "am.make_room": 6, "bench.append": 2}
    assert sum(gaps.values()) == 80


def test_innermost_segments():
    spans = [["a", 0, 10], ["b", 2, 3], ["c", 6, 4]]
    assert hostspans.innermost(spans) == [[0, 2, "a"], [2, 5, "b"],
                                          [5, 6, "a"], [6, 10, "c"]]


def _unpacked(tmp_path, name):
    raw = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(DATA, name)) as f, open(raw, "wb") as out:
        shutil.copyfileobj(f, out)
    return str(raw)


def test_without_program_spans_the_rule_gives_the_old_attribution(tmp_path):
    hs = hostspans.extract(_unpacked(tmp_path, "sift_steady_2s.xplane.pb.gz"))
    trace = tracereduce.Trace.load(os.path.join(DATA,
                                                "sift_steady_2s.json.gz"))
    assert hostspans.threads(hs) == (7, None)
    assert hostspans.idle_gaps(trace, hs) == tracereduce.idle_gaps(trace)
    assert hostspans.launch_host_ms(hs) is None
    assert hostspans.make_room_ms(hs) is None
    assert hostspans.slab_write_ms(hs) is None


def test_queue_wait_reader():
    read = catalog.load_reader(HERE, "queue_wait_ms.tail")
    ctx = types.SimpleNamespace(
        before={"dispatched": 100, "queue_wait_s": 10.0},
        after={"dispatched": 300, "queue_wait_s": 30.5})
    assert read(ctx) == pytest.approx(102.5)
    # a service that keeps no such counter reads nothing
    ctx.before, ctx.after = {"dispatched": 100}, {"dispatched": 300}
    assert read(ctx) is None


@pytest.fixture(scope="module")
def latest():
    return (tracereduce.Trace.load(os.path.join(DATA,
                                                "ycsb_latest_2s.json.gz")),
            hostspans.HostSpans.load(os.path.join(
                DATA, "ycsb_latest_2s.spans.json.gz")))


def test_extract_reads_the_recorded_program_spans(tmp_path, latest):
    raw = _unpacked(tmp_path, "ycsb_latest_2s.xplane.pb.gz")
    trace, hs = latest
    assert hostspans.extract(raw) == hs
    assert tracereduce.extract(raw) == trace
    # the client thread records the benchmark's spans and appends; the
    # driver thread waits and launches
    client, driver = hostspans.threads(hs)
    assert (client, driver) == (22, 21)
    assert {sp[0] for sp in hs.spans if sp[3] == client} == {
        "bench.submit", "bench.result", "bench.append", "am.append",
        "am.make_room", "am.write"}


def test_span_reductions_on_the_recorded_trace(latest):
    _, hs = latest
    # ten inserts: each an append whose meta readback and eviction check
    # take all but about 2 ms; two slab writes of 0.7 ms each
    assert len(hostspans.in_window(hs, "am.append")) == 10
    assert hostspans.make_room_ms(hs) == 192.7522815
    assert hostspans.slab_write_ms(hs) == 1.3873745
    assert hostspans.launch_host_ms(hs) == 2.64936
    groups = {sp[4]["group"] for sp in hs.spans if sp[0] == "am.launch"}
    assert groups == {sp[4]["group"] for sp in hs.spans
                      if sp[0] == "am.readback"}


def test_idle_attribution_on_the_recorded_trace(latest):
    trace, hs = latest
    gaps = hostspans.idle_gaps(trace, hs, n=20)
    assert gaps[:5] == [["am.make_room", 1.268625401],
                        ["am.write", 0.013966531],
                        ["am.append", 0.009090751],
                        ["am.launch", 0.008860676],
                        ["no_span", 0.003497128]]
    assert len(gaps) == 11
    idle = tracereduce.window_s(trace) - tracereduce.busy_s(trace)
    assert sum(s for _, s in gaps) == pytest.approx(idle, abs=1e-9)
    # what the benchmark's own spans saw: the same idle time, in bench.append
    assert tracereduce.idle_gaps(trace)[0] == ["bench.append", 1.292085484]
