"""The pipelined dispatch driver + admission control, deterministically.

Most tests here run the :class:`AMDriver` *unstarted* — stepping
``run_once(now=...)`` by hand against a fake-clock service — so every
dispatch and completion happens at an exact, replayable point.  That is how
the two load-bearing claims are proven:

* the **dead-deadline regression**: on the pre-driver code a half-full
  bucket under `flush_after` with the default logical clock waited forever
  (``poll()`` compared a frozen clock); now construction warns and a
  clock-owning driver fires the deadline with zero further submits;
* the **bitwise contract**: the async pipeline (launch stage, in-flight
  queue, deferred completion stage) resolves interleaved
  submit/append/evict/delete traffic to byte-identical responses as the
  synchronous :meth:`AMService.flush` reference path.

A real background-thread smoke test and a thread-leak teardown assertion
close the loop on the threaded mode.
"""

import threading
import time

import numpy as np
import pytest

from repro.serve.am_service import (ADMISSION_MODES, COMPLETION_ORDER,
                                    DRIVER_STATES, AdmissionError, AMDriver,
                                    AMService)

WIDTH = 8
LEVELS = 8      # bits=3


@pytest.fixture(autouse=True)
def _no_thread_leaks():
    """Every driver thread started by a test must be joined by teardown."""
    before = set(threading.enumerate())
    yield
    time.sleep(0)           # let a just-joined thread finish dying
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    assert not leaked, f"test leaked threads: {leaked}"


def _svc(clock=None, **kw):
    time_fn = (lambda: clock[0]) if clock is not None else None
    svc = AMService(time_fn=time_fn, **kw)
    svc.create_table("t", width=WIDTH, capacity=32, policy="lru",
                     backend="ref")
    return svc


def _codes(rng, n):
    return rng.integers(0, LEVELS, (n, WIDTH)).astype(np.int32)


# ---------------------------------------------------------------------------
# the dead-deadline bug: regression tests
# ---------------------------------------------------------------------------

def test_flush_after_without_real_clock_warns():
    """REGRESSION (fails pre-PR): flush_after on the logical clock used to
    be accepted silently even though poll() could never fire it."""
    with pytest.warns(RuntimeWarning, match="logical clock"):
        AMService(flush_after=0.01)


def test_no_warning_with_real_clock_or_no_deadline():
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        AMService()                                     # no deadline: quiet
        AMService(flush_after=0.01, time_fn=time.monotonic)


def test_driver_fires_deadline_with_zero_further_submits():
    """The idle-traffic gap itself: a half-full bucket, submits stop, only
    the clock advances — the driver must dispatch it."""
    clock = [100.0]
    rng = np.random.default_rng(0)
    svc = _svc(clock, flush_after=2.0, max_batch=64)
    svc.append("t", _codes(rng, 8))
    drv = AMDriver(svc)
    fut = svc.submit("t", _codes(rng, 1)[0])
    # deadline not reached: stepping the driver is a no-op, however often
    for _ in range(5):
        assert drv.run_once() == {"launched": 0, "completed": 0}
    assert not fut.done and svc.stats()["pending"] == 1
    clock[0] += 2.5                                     # ONLY time moves
    r = drv.run_once()
    assert r["launched"] == 1 and r["completed"] == 1
    assert fut.done and svc.stats()["pending"] == 0


def test_background_driver_refuses_logical_clock_deadline():
    with pytest.warns(RuntimeWarning, match="logical clock"):
        svc = AMService(flush_after=1.0)
    svc.create_table("t", width=WIDTH, capacity=8)
    with pytest.raises(ValueError, match="logical clock"):
        svc.start_driver()


# ---------------------------------------------------------------------------
# async == sync, bitwise, on interleaved traffic
# ---------------------------------------------------------------------------

def _interleaved_trace(svc, drv, rng, *, step=None):
    """Run interleaved submit/append/evict/delete traffic; return responses.

    ``step`` is called between operations when given (the async variant
    steps the driver there); the sync variant relies on flush()/result().
    """
    svc.append("t", _codes(rng, 8),
               values=[f"v{i}" for i in range(8)])
    futs = []
    for wave in range(4):
        for _ in range(5):
            futs.append(svc.submit("t", _codes(rng, 1)[0], k=3))
        if step:
            step(force=False)
        svc.append("t", _codes(rng, 4),
                   values=[f"w{wave}.{i}" for i in range(4)])
        if wave == 1:
            svc.delete("t", [0, 2])
        if wave == 2:
            svc.evict("t")
        if step:
            step(force=True)          # fully drain before the next wave
    if step:
        step(force=True)
    return [f.result() for f in futs]


def test_async_bitwise_identical_to_sync():
    mk = lambda: _svc(max_batch=5)    # noqa: E731
    rng_a, rng_b = (np.random.default_rng(42) for _ in range(2))

    svc_sync = mk()
    sync = _interleaved_trace(svc_sync, None, rng_a)

    svc_async = mk()
    drv = AMDriver(svc_async, max_in_flight=4)
    def step(force):
        drv.run_once(force=force)
    async_ = _interleaved_trace(svc_async, drv, rng_b, step=step)

    assert len(sync) == len(async_) == 20
    for rs, ra in zip(sync, async_):
        assert rs.rid == ra.rid and rs.table == ra.table
        np.testing.assert_array_equal(rs.indices, ra.indices)
        np.testing.assert_array_equal(
            rs.distances.tobytes(), ra.distances.tobytes())   # bitwise
        np.testing.assert_array_equal(rs.exact, ra.exact)
        np.testing.assert_array_equal(rs.matched, ra.matched)
        assert rs.value == ra.value
    # and the tables ended in the same state (meta included)
    ts, ta = svc_sync._tables["t"], svc_async._tables["t"]
    assert ts.n == ta.n and ts.values == ta.values
    np.testing.assert_array_equal(np.asarray(ts.table.codes),
                                  np.asarray(ta.table.codes))
    np.testing.assert_array_equal(np.asarray(ts.table.meta),
                                  np.asarray(ta.table.meta))


def test_append_overlaps_in_flight_group():
    """An append between launch and completion must not disturb the
    dispatched snapshot: payload fan-out uses launch-time row indices, and
    the stale LRU touch is dropped (version check) rather than clobbering
    the new rows' meta."""
    rng = np.random.default_rng(3)
    svc = _svc(max_batch=64)
    codes = _codes(rng, 4)
    svc.append("t", codes, values=["a", "b", "c", "d"])
    drv = AMDriver(svc, max_in_flight=4)
    fut = svc.submit("t", codes[2], k=1)
    r = drv.run_once(force=True)      # force launches... and completes
    assert r == {"launched": 1, "completed": 1}
    assert fut.result().value == "c"

    # now do it with the completion held back behind an append
    fut2 = svc.submit("t", codes[1], k=1)
    with svc._lock:
        svc._launch_pending(svc._tick())
    meta_version = svc._tables["t"].version
    svc.append("t", _codes(rng, 2), values=["x", "y"])      # overlaps
    assert svc.stats()["in_flight"] == 1
    assert drv.run_once()["completed"] == 1
    assert fut2.result().value == "b"                       # snapshot index
    # the deferred touch lost the version race and was dropped
    assert svc._tables["t"].version == meta_version + 1
    assert svc.stats("t")["rows"] == 6


def test_in_flight_groups_complete_fifo():
    assert COMPLETION_ORDER == "fifo"
    rng = np.random.default_rng(4)
    svc = _svc(max_batch=64)
    svc.append("t", _codes(rng, 8))
    f1 = svc.submit("t", _codes(rng, 1)[0])
    with svc._lock:
        svc._launch_pending(svc._tick())
    f2 = svc.submit("t", _codes(rng, 1)[0], k=2)     # second group
    with svc._lock:
        svc._launch_pending(svc._tick())
    assert svc.stats()["in_flight"] == 2
    assert svc._complete_next()       # retires the OLDEST group
    assert f1.done and not f2.done
    assert svc._complete_next()
    assert f2.done
    assert not svc._complete_next()   # drained


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_admission_reject_counts_and_raises():
    rng = np.random.default_rng(5)
    svc = AMService(max_batch=64)
    svc.create_table("t", width=WIDTH, capacity=32, max_queue=2,
                     admission="reject")
    svc.append("t", _codes(rng, 4))
    svc.submit("t", _codes(rng, 1)[0])
    svc.submit("t", _codes(rng, 1)[0])
    with pytest.raises(AdmissionError, match="max_queue"):
        svc.submit("t", _codes(rng, 1)[0])
    s = svc.stats()
    assert s["admission"]["rejected"] == 1
    assert s["queue_depth"] == 2
    assert svc.stats("t")["rejected"] == 1
    svc.flush()                       # admitted lookups still resolve


def test_admission_shed_resolves_as_unadmitted_miss():
    rng = np.random.default_rng(6)
    svc = AMService(max_batch=64)
    svc.create_table("t", width=WIDTH, capacity=32, max_queue=1,
                     admission="shed")
    svc.append("t", _codes(rng, 4))
    f1 = svc.submit("t", _codes(rng, 1)[0])
    f2 = svc.submit("t", _codes(rng, 1)[0])          # over the cap: shed
    assert f2.done and not f2.result().admitted and not f2.result().hit
    assert svc.stats("t")["shed"] == 1
    svc.flush()
    assert f1.done and f1.result().admitted


def test_admission_qps_token_bucket():
    clock = [0.0]
    svc = AMService(time_fn=lambda: clock[0], max_batch=64)
    svc.create_table("t", width=WIDTH, capacity=32, qps_budget=2.0,
                     burst=2.0, admission="reject")
    rng = np.random.default_rng(7)
    svc.append("t", _codes(rng, 4))
    q = _codes(rng, 1)[0]
    svc.submit("t", q)
    svc.submit("t", q)                               # burst of 2 spent
    with pytest.raises(AdmissionError, match="qps_budget"):
        svc.submit("t", q)
    clock[0] += 0.5                                  # refills 1 token
    svc.submit("t", q)
    assert svc.stats("t")["rejected"] == 1
    svc.flush()


def test_admission_block_waits_for_queue_headroom():
    rng = np.random.default_rng(8)
    svc = AMService(max_batch=64)
    svc.create_table("t", width=WIDTH, capacity=32, max_queue=1,
                     admission="block")
    svc.append("t", _codes(rng, 4))
    f1 = svc.submit("t", _codes(rng, 1)[0])
    f2 = svc.submit("t", _codes(rng, 1)[0])   # blocks -> self-flushes f1
    assert f1.done and not f2.done
    assert svc.stats("t")["blocked"] == 1
    svc.flush()
    assert f2.done


def test_admission_block_on_qps_needs_real_clock():
    svc = AMService(max_batch=64)
    # under the logical clock each submit advances one tick, so the budget
    # must be < 1 per tick to ever run dry
    svc.create_table("t", width=WIDTH, capacity=32, qps_budget=0.25,
                     admission="block")
    rng = np.random.default_rng(9)
    svc.append("t", _codes(rng, 4))
    svc.submit("t", _codes(rng, 1)[0])
    with pytest.raises(AdmissionError, match="real clock"):
        svc.submit("t", _codes(rng, 1)[0])
    svc.flush()


def test_admission_modes_constant():
    assert ADMISSION_MODES == ("reject", "shed", "block")
    with pytest.raises(ValueError, match="admission"):
        AMService().create_table("t", width=WIDTH, admission="drop")


# ---------------------------------------------------------------------------
# lifecycle: drop_table with in-flight work, driver states, real threads
# ---------------------------------------------------------------------------

def test_drop_table_with_in_flight_group_loses_no_future():
    rng = np.random.default_rng(10)
    svc = _svc(max_batch=64)
    codes = _codes(rng, 4)
    svc.append("t", codes, values=["a", "b", "c", "d"])
    fut = svc.submit("t", codes[3], k=1)
    with svc._lock:
        svc._launch_pending(svc._tick())             # in flight, unread
    assert svc.stats()["in_flight"] == 1
    svc.drop_table("t")                              # resolves it first
    assert fut.done and fut.result().value == "d"
    with pytest.raises(ValueError, match="unknown table"):
        svc.submit("t", codes[0])


def test_driver_states_and_stats():
    assert DRIVER_STATES == ("idle", "running", "draining", "stopped")
    svc = AMService(time_fn=time.monotonic)
    svc.create_table("t", width=WIDTH, capacity=8)
    drv = AMDriver(svc)
    assert drv.state == "idle"
    assert svc.stats()["driver"] is None             # not attached
    drv = svc.start_driver()
    assert drv.state == "running" and svc.stats()["driver"] == "running"
    with pytest.raises(RuntimeError, match="already running"):
        svc.start_driver()
    svc.stop_driver()
    assert drv.state == "stopped" and not drv.is_alive()
    assert svc.stats()["driver"] is None


def test_background_driver_end_to_end():
    """Real thread, real clock: deadline-dispatched lookups resolve through
    result(timeout) with no explicit flush anywhere."""
    rng = np.random.default_rng(11)
    svc = AMService(max_batch=64, flush_after=0.005,
                    time_fn=time.monotonic)
    svc.create_table("t", width=WIDTH, capacity=32)
    codes = _codes(rng, 8)
    svc.append("t", codes, values=[f"v{i}" for i in range(8)])
    svc.start_driver()
    try:
        futs = [svc.submit("t", codes[i % 8], k=2) for i in range(12)]
        resps = [f.result(timeout=30.0) for f in futs]
        for i, r in enumerate(resps):
            assert r.hit and r.value == f"v{i % 8}"
        assert svc.drain(timeout=5.0)
        s = svc.stats()
        assert s["pending"] == 0 and s["in_flight"] == 0
        # every lookup launched waited a real, non-negative time
        assert svc.dispatched == 12 and s["queue_wait_s"] >= 0.0
    finally:
        svc.stop_driver()


def test_stats_surface_queue_and_wait_percentiles():
    rng = np.random.default_rng(12)
    svc = _svc(max_batch=64)
    svc.append("t", _codes(rng, 8))
    svc.submit("t", _codes(rng, 1)[0])
    s = svc.stats()
    assert s["queue_depth"] == 1 and s["in_flight"] == 0
    assert {"rejected", "shed", "blocked"} <= set(s["admission"])
    svc.flush()
    s = svc.stats()
    assert s["queue_depth"] == 0
    # the logical clock ticked once between the submit and the flush
    assert s["queue_wait_s"] == 1.0


# ---------------------------------------------------------------------------
# satellite: delete() index validation (service + core)
# ---------------------------------------------------------------------------

def test_service_delete_rejects_out_of_range_indices():
    rng = np.random.default_rng(13)
    svc = _svc()
    svc.append("t", _codes(rng, 4), values=["a", "b", "c", "d"])
    with pytest.raises(ValueError, match=r"\[-1\]"):
        svc.delete("t", [-1])                        # used to wrap to row 3
    with pytest.raises(ValueError, match=r"\[7\]"):
        svc.delete("t", [1, 7])
    assert svc.stats("t")["rows"] == 4               # nothing was deleted
    assert svc.delete("t", [3]) == 1
    assert svc._tables["t"].values == ["a", "b", "c"]


def test_core_delete_rejects_out_of_range_indices():
    import jax.numpy as jnp

    from repro.core import am
    t = am.make_table(jnp.arange(12, dtype=jnp.int32).reshape(4, 3), bits=3)
    with pytest.raises(ValueError, match=r"\[-2\]"):
        am.delete(t, [-2])
    with pytest.raises(ValueError, match=r"\[4\]"):
        am.delete(t, [0, 4])
    assert am.delete(t, [0]).n_rows == 3


# ---------------------------------------------------------------------------
# satellite: k >= 1 validation
# ---------------------------------------------------------------------------

def test_k_validation_at_every_entry():
    import jax.numpy as jnp

    from repro.core import am
    rng = np.random.default_rng(14)
    svc = _svc()
    svc.append("t", _codes(rng, 4))
    for bad_k in (0, -3):
        with pytest.raises(ValueError, match="k must be >= 1"):
            svc.submit("t", _codes(rng, 1)[0], k=bad_k)
    t = am.make_table(jnp.zeros((4, 3), jnp.int32), bits=3)
    q = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="k must be >= 1"):
        am.search(t, q, k=0)
    import jax
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="k must be >= 1"):
        am.search_sharded(t, q, mesh=mesh, k=-1)


# ---------------------------------------------------------------------------
# review regressions: sync-path races, drop_table windows, budget livelock
# ---------------------------------------------------------------------------

def _park_readback(svc):
    """Patch the completion stage to park until a gate opens.

    Returns (started, gate): ``started`` fires once a flusher has claimed a
    group and is inside the (parked) readback; ``gate`` releases it.
    """
    started, gate = threading.Event(), threading.Event()
    real = svc._resolve_group

    def slow(g):
        started.set()
        assert gate.wait(10.0)
        real(g)

    svc._resolve_group = slow
    return started, gate


def test_result_waits_out_concurrent_sync_flush():
    """Driverless concurrent callers: a thread calling result() while
    another thread's flush() holds its bucket mid-readback must wait on
    the completion stage — the pre-fix code did a no-op flush and hit
    `assert self._response is not None` (or returned None under -O)."""
    rng = np.random.default_rng(20)
    svc = _svc(max_batch=64)
    codes = _codes(rng, 4)
    svc.append("t", codes, values=["a", "b", "c", "d"])
    f1 = svc.submit("t", codes[0])
    f2 = svc.submit("t", codes[1])
    started, gate = _park_readback(svc)
    flusher = threading.Thread(target=svc.flush)
    flusher.start()
    out = {}
    try:
        assert started.wait(10.0)        # bucket claimed, readback parked
        waiter = threading.Thread(
            target=lambda: out.setdefault("r", f2.result(timeout=10.0)))
        waiter.start()
        waiter.join(0.2)
        assert waiter.is_alive()         # waiting on the event, not dead
        gate.set()
        waiter.join(10.0)
        assert not waiter.is_alive()
    finally:
        gate.set()
        flusher.join(10.0)
    assert out["r"].hit and out["r"].value == "b"
    assert f1.result(timeout=1.0).value == "a"


def test_drain_sync_path_waits_for_midflight_readback():
    """drain() without a driver must not report quiescence while another
    thread holds a popped group mid-readback (futures still unresolved)."""
    rng = np.random.default_rng(21)
    svc = _svc(max_batch=64)
    codes = _codes(rng, 2)
    svc.append("t", codes, values=["a", "b"])
    fut = svc.submit("t", codes[0])
    started, gate = _park_readback(svc)
    flusher = threading.Thread(target=svc.flush)
    flusher.start()
    out = {}
    try:
        assert started.wait(10.0)
        drainer = threading.Thread(
            target=lambda: out.setdefault("ok", svc.drain(timeout=10.0)))
        drainer.start()
        drainer.join(0.2)
        # pre-fix: drain returned True here with fut still unresolved
        assert drainer.is_alive() or fut.done
        gate.set()
        drainer.join(10.0)
        assert not drainer.is_alive()
    finally:
        gate.set()
        flusher.join(10.0)
    assert out["ok"] is True and fut.done
    assert fut.result().value == "a"


def test_flush_tolerates_table_dropped_after_queueing():
    """The drop_table race window: a lookup queued for a table that
    vanishes before the flush drains it resolves as a miss — the pre-fix
    `_take_pending` raised KeyError and orphaned every drained future."""
    rng = np.random.default_rng(22)
    svc = _svc(max_batch=64)
    codes = _codes(rng, 2)
    svc.append("t", codes)
    fut = svc.submit("t", codes[0])
    with svc._lock:
        del svc._tables["t"]          # simulate the submit/drop interleaving
    svc.flush()
    assert fut.done
    r = fut.result()
    assert not r.hit and r.admitted and r.indices[0] == -1


def test_qps_budget_refills_under_logical_clock():
    """Over-budget submits advance the logical clock, so an exhausted
    token bucket refills from continued traffic — pre-fix, reject/shed
    never ticked and the budget livelocked at zero tokens forever."""
    rng = np.random.default_rng(23)
    svc = AMService(max_batch=64)
    svc.create_table("t", width=WIDTH, capacity=32, qps_budget=0.5,
                     burst=1.0, admission="shed")
    svc.append("t", _codes(rng, 4))
    q = _codes(rng, 1)[0]
    admitted = []
    for _ in range(5):
        f = svc.submit("t", q)
        admitted.append(not (f.done and not f.result().admitted))
    # 0.5 tokens per tick: every other submit is admitted after the burst
    assert admitted == [True, False, True, False, True]
    assert svc.stats("t")["shed"] == 2
    svc.flush()
