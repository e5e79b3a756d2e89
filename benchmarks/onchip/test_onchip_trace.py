"""The trace reduction, pinned on a trace recorded on a TPU v5e.

``testdata/sift_steady_2s.*`` is a 2-second ``--trace 1`` window of the
``sift1m_l1.steady_k10`` cell (a TPU v5 lite, 200 lookups/s offered): the
raw ``.xplane.pb`` the profiler wrote, gzipped, and its reduced form.
"""

import gzip
import os
import shutil
import statistics
import types

import pytest

from onchip import kernels, roofline, tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "testdata")
SIFT = {"rows": 1000000, "width": 128, "bits": 3}


@pytest.fixture(scope="module")
def trace():
    return tracereduce.Trace.load(os.path.join(DATA, "sift_steady_2s.json.gz"))


def test_extract_reads_the_recorded_xplane(tmp_path, trace):
    raw = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(DATA, "sift_steady_2s.xplane.pb.gz")) as f, \
            open(raw, "wb") as out:
        shutil.copyfileobj(f, out)
    assert tracereduce.extract(str(raw)) == trace


def test_busy_and_idle(trace):
    assert tracereduce.window_s(trace) == 2.00087035
    assert tracereduce.busy_s(trace) == 1.994279164
    assert tracereduce.idle_share(trace) == pytest.approx(0.0032941595)


def test_kernel_calls_by_stable_name(trace):
    calls = tracereduce.kernel_calls(trace, kernels.KERNEL)
    assert [tracereduce.call_rows(op) for op in calls] == [
        8, 8, 8, 8, 32, 16, 16, 64, 32, 32, 64, 32, 64]
    assert statistics.median(op[2] for op in calls) == 119934313
    # one 8-row block of the table scan costs ~30 ms at every bucket
    for op in calls:
        per_block = op[2] / (tracereduce.call_rows(op) // 8)
        assert per_block == pytest.approx(30.0e6, rel=0.005)


def test_prep_per_dispatch(trace):
    prep = tracereduce.dispatch_prep(trace, "dispatch", kernels.KERNEL)
    assert len(prep) == 13
    assert statistics.median(prep) == 0.045911612


def test_breakdown(trace):
    top = tracereduce.top_ops(trace)
    assert top[0] == ["cam_search_topk.1 (s32[64,128], f32[64,128]) "
                      "custom-call", 0.719638124]
    assert top[2] == ["reshape.8 s32[1000000,896] reshape", 0.180185965]
    assert len(top) == 10
    assert tracereduce.idle_gaps(trace) == [["no_span", 0.003609207],
                                            ["bench.submit", 0.002981979]]


def test_roofline_counts_the_configurations_own_work(trace):
    ctx = types.SimpleNamespace(
        trace=trace, cfg=SIFT, cell=types.SimpleNamespace(traffic={"k": 10}),
        peak=roofline.peaks("TPU v5 lite"))
    # 10^6 x 128 cells at 3 bits is 48 MB: 58.6 us at 819 GB/s, against
    # 30-240 ms per call
    t, bound = roofline.topk_least_time(8, 1000000, 128, 3, 10, ctx.peak)
    assert bound == "memory"
    assert t == pytest.approx((48e6 + 8 * 128 + 8 * 10 * 8) / 819e9)
    t, bound = roofline.topk_least_time(4096, 1000000, 128, 3, 10, ctx.peak)
    assert bound == "compute" and t == pytest.approx(2 * 4096 * 128e6 / 393e12)
    assert kernels.roofline_pct(ctx) == pytest.approx(0.0529107, rel=1e-5)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.peaks("TPU v99")


def test_union_and_gaps_on_a_small_trace():
    tr = tracereduce.Trace(
        ops=[["%a = s32[2] add(x)", 10, 10], ["%b = s32[2] add(x)", 15, 10],
             ["%c = s32[2] mul(x)", 40, 5], ["%d = s32[2] mul(x)", 90, 20]],
        modules=[], spans=[["bench.submit", 25, 10], ["bench.result", 60, 50]],
        window=[0, 100])
    assert tracereduce.busy(tr) == [[10, 25], [40, 45], [90, 100]]
    assert tracereduce.busy_s(tr) == 30e-9
    assert tracereduce.idle_share(tr) == pytest.approx(0.7)
    assert dict(tracereduce.idle_gaps(tr)) == {
        "no_span": 30e-9, "bench.result": 30e-9, "bench.submit": 10e-9}
