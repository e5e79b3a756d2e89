"""AMService: micro-batched scheduler correctness, compile accounting,
table lifecycle and eviction policies, and sharded placement.

The scheduler contract under test (the PR's acceptance criteria):
  * any interleaving of submits/flushes returns results bitwise-identical
    to direct ``am.search`` on the live rows;
  * at most ONE compilation per (bucket, k, backend, thresholded) dispatch
    signature, and one host readback per dispatched group;
  * a capacity-bounded table never exceeds its capacity (LRU and TTL).
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import am
from repro.serve.am_service import (AMService, SearchResponse,
                                    TableFullError, _meta_column, _next_pow2)

WIDTH = 6


def _svc(capacity=32, width=WIDTH, policy="lru", ttl=None, backend="ref",
         **kw) -> AMService:
    svc = AMService(**kw)
    svc.create_table("t", width=width, bits=3, capacity=capacity,
                     policy=policy, ttl=ttl, backend=backend)
    return svc


def _codes(rng, n, width=WIDTH):
    return rng.integers(0, 8, (n, width)).astype(np.int32)


# ---------------------------------------------------------------------------
# basic round trips
# ---------------------------------------------------------------------------

def test_lookup_hit_returns_payload_and_topk():
    rng = np.random.default_rng(0)
    svc = _svc()
    codes = _codes(rng, 10)
    svc.append("t", codes, values=[f"v{i}" for i in range(10)])
    r = svc.lookup("t", codes[3], k=2)
    assert isinstance(r, SearchResponse)
    assert r.hit and r.best_row == 3 and r.value == "v3"
    assert r.indices.shape == (2,) and r.distances[0] == 0.0
    miss = svc.lookup("t", (codes[3] + 1) % 8)
    assert not miss.hit and miss.value is None
    assert svc.stats("t") == {**svc.stats("t"), "hits": 1, "misses": 1}


def test_empty_table_resolves_immediate_miss():
    svc = _svc()
    fut = svc.submit("t", np.zeros(WIDTH, np.int32), k=3)
    assert fut.done                       # no dispatch needed
    r = fut.result()
    assert not r.hit and r.value is None
    np.testing.assert_array_equal(r.indices, [-1, -1, -1])
    assert np.all(np.isinf(r.distances))
    assert svc.stats()["readbacks"] == 0 and svc.stats()["compilations"] == 0


def test_more_live_rows_than_k_entries_padded():
    """k beyond the live rows: surplus entries are -1 / inf / False."""
    rng = np.random.default_rng(1)
    svc = _svc(capacity=16)
    codes = _codes(rng, 3)
    svc.append("t", codes, values=[0, 1, 2])
    r = svc.lookup("t", codes[0], k=5)
    assert r.indices.shape == (5,)
    assert np.all(r.indices[3:] == -1) and np.all(np.isinf(r.distances[3:]))
    assert not r.exact[3:].any() and not r.matched[3:].any()
    want = am.search(am.make_table(codes, bits=3), codes[0], k=3)
    np.testing.assert_array_equal(r.indices[:3], np.asarray(want.indices))
    np.testing.assert_array_equal(r.distances[:3], np.asarray(want.distances))


def test_validation_errors():
    svc = _svc(capacity=4)
    with pytest.raises(ValueError):
        svc.create_table("t", width=4)            # duplicate name
    with pytest.raises(ValueError):
        svc.create_table("u", width=4, policy="fifo")
    with pytest.raises(ValueError):
        svc.create_table("u", width=4, policy="ttl")          # ttl missing
    with pytest.raises(ValueError):
        svc.create_table("u", width=4, policy="lru", ttl=3.0)  # ttl spurious
    with pytest.raises(ValueError):
        svc.create_table("u", width=4, backend="cuda")
    with pytest.raises(ValueError):
        svc.lookup("nope", np.zeros(WIDTH, np.int32))
    with pytest.raises(ValueError):
        svc.submit("t", np.zeros(WIDTH + 1, np.int32))
    with pytest.raises(ValueError):
        svc.append("t", np.zeros((1, WIDTH + 2), np.int32))
    with pytest.raises(ValueError):
        svc.append("t", np.zeros((2, WIDTH), np.int32), values=[1])
    with pytest.raises(TableFullError):
        svc.append("t", np.zeros((5, WIDTH), np.int32))   # > capacity at once


# ---------------------------------------------------------------------------
# scheduler: interleavings are bitwise-identical to direct am.search
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_any_interleaving_matches_direct_search(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 24))
    codes = _codes(rng, n)
    svc = _svc(capacity=32, max_batch=int(rng.integers(2, 12)))
    svc.append("t", codes, values=list(range(n)))
    oracle = am.make_table(codes, bits=3)

    futs = []
    for _ in range(int(rng.integers(5, 40))):
        if rng.random() < 0.2:
            svc.flush()
        q = rng.integers(0, 8, (WIDTH,)).astype(np.int32)
        if rng.random() < 0.3:                      # force some exact hits
            q = codes[rng.integers(n)]
        k = int(rng.integers(1, 7))
        thr = None if rng.random() < 0.5 else float(rng.integers(0, 10))
        futs.append((q, k, thr, svc.submit("t", q, k=k, threshold=thr)))
    svc.flush()

    for q, k, thr, fut in futs:
        got = fut.result()
        kn = min(k, n)
        want = am.search(oracle, q, k=kn, threshold=thr)
        np.testing.assert_array_equal(got.indices[:kn],
                                      np.asarray(want.indices))
        np.testing.assert_array_equal(got.distances[:kn],
                                      np.asarray(want.distances))
        np.testing.assert_array_equal(got.exact[:kn], np.asarray(want.exact))
        np.testing.assert_array_equal(got.matched[:kn],
                                      np.asarray(want.matched))
        assert np.all(got.indices[kn:] == -1)


def test_mixed_signature_flush_routes_every_request():
    """One flush with mixed k/threshold groups fans out correctly."""
    rng = np.random.default_rng(3)
    codes = _codes(rng, 12)
    svc = _svc()
    svc.append("t", codes, values=list(range(12)))
    oracle = am.make_table(codes, bits=3)
    futs = ([svc.submit("t", codes[i], k=1) for i in range(4)]
            + [svc.submit("t", codes[i], k=3, threshold=2.0)
               for i in range(4)])
    served = svc.flush()
    assert served == 8
    assert svc.stats()["readbacks"] == 2           # one per signature group
    for i, fut in enumerate(futs):
        assert fut.result().hit and fut.result().value == i % 4
    want = am.search(oracle, codes[0], k=3, threshold=2.0)
    np.testing.assert_array_equal(futs[4].result().indices,
                                  np.asarray(want.indices))


# ---------------------------------------------------------------------------
# compile accounting: exactly one compilation per bucket signature
# ---------------------------------------------------------------------------

def test_one_compilation_per_bucket_signature():
    rng = np.random.default_rng(4)
    svc = _svc(capacity=64)
    svc.append("t", _codes(rng, 20), values=list(range(20)))

    def flush_n(n, k=1):
        for _ in range(n):
            svc.submit("t", rng.integers(0, 8, (WIDTH,)), k=k)
        svc.flush()

    flush_n(3)                                     # bucket 4, k=1 -> compile
    assert svc.stats()["compilations"] == 1
    flush_n(4)                                     # bucket 4 again -> cached
    assert svc.stats()["compilations"] == 1
    svc.append("t", _codes(rng, 5))                # append must NOT recompile
    flush_n(2)                                     # still bucket 4? no: 2
    assert svc.stats()["compilations"] == 2        # bucket 2 is new
    flush_n(4)
    assert svc.stats()["compilations"] == 2        # bucket 4 still cached
    flush_n(5)                                     # bucket 8 -> new
    assert svc.stats()["compilations"] == 3
    flush_n(4, k=2)                                # same bucket, new k -> new
    assert svc.stats()["compilations"] == 4
    flush_n(4, k=2)
    assert svc.stats()["compilations"] == 4


def test_acceptance_smoke_64_mixed_lookups():
    """The ISSUE acceptance run: >= 64 mixed lookups against a
    capacity-bounded table — bitwise-identical to direct search, one
    compilation per signature, capacity never exceeded."""
    rng = np.random.default_rng(5)
    svc = _svc(capacity=16, max_batch=16)
    pop = _codes(rng, 40)

    checked = 0
    signatures = set()
    for step in range(72):
        q = pop[rng.integers(40)]
        k = int(rng.choice([1, 4]))
        fut = svc.submit("t", q, k=k)
        live = am.make_table(np.asarray(svc._tables["t"].table.codes
                                        [:svc._tables["t"].n]), bits=3) \
            if svc._tables["t"].n else None
        resp = fut.result()                         # flushes queue
        assert svc.stats("t")["rows"] <= 16
        if live is not None:
            kn = min(k, live.n_rows)
            want = am.search(live, q, k=kn)
            np.testing.assert_array_equal(resp.indices[:kn],
                                          np.asarray(want.indices))
            np.testing.assert_array_equal(resp.distances[:kn],
                                          np.asarray(want.distances))
            checked += 1
            signatures.add((1, k))                  # bucket is 1: sync loop
        if not resp.hit:
            svc.append("t", q, values=[step])
    assert checked >= 64
    assert svc.stats()["compilations"] <= len(signatures)
    assert svc.stats("t")["evicted"] > 0            # capacity really bound


# ---------------------------------------------------------------------------
# auto-flush knobs
# ---------------------------------------------------------------------------

def test_max_batch_autoflush():
    rng = np.random.default_rng(6)
    svc = _svc(max_batch=4)
    svc.append("t", _codes(rng, 8))
    futs = [svc.submit("t", rng.integers(0, 8, (WIDTH,))) for _ in range(4)]
    assert all(f.done for f in futs)               # 4th submit flushed
    assert svc.stats()["pending"] == 0 and svc.stats()["flushes"] == 1


def test_flush_after_deadline():
    rng = np.random.default_rng(7)
    with pytest.warns(RuntimeWarning, match="logical clock"):
        svc = _svc(flush_after=2.0)                # logical-clock units
    svc.append("t", _codes(rng, 8))
    f1 = svc.submit("t", rng.integers(0, 8, (WIDTH,)))
    f2 = svc.submit("t", rng.integers(0, 8, (WIDTH,)))
    assert not f1.done and not f2.done
    f3 = svc.submit("t", rng.integers(0, 8, (WIDTH,)))   # 3 ticks elapsed
    assert f1.done and f2.done and f3.done


def test_poll_flushes_expired_bucket_under_idle_traffic():
    """The stale-deadline gap: without poll(), a half-full bucket waits
    forever once submits stop.  A clock-injected service proves poll()
    observes the wall deadline without advancing it."""
    clock = [100.0]
    rng = np.random.default_rng(70)
    svc = _svc(flush_after=2.0, time_fn=lambda: clock[0])
    svc.append("t", _codes(rng, 8))
    codes = _codes(rng, 2)
    f1 = svc.submit("t", codes[0])
    clock[0] += 1.0
    f2 = svc.submit("t", codes[1])
    assert not f1.done and not f2.done
    # deadline not reached: poll is a no-op, however often it runs
    for _ in range(10):
        assert svc.poll() == 0
    assert not f1.done and not f2.done and svc.stats()["pending"] == 2
    # the oldest request crosses the deadline: one poll serves the bucket
    clock[0] += 1.5
    assert svc.poll() == 2
    assert f1.done and f2.done and svc.stats()["pending"] == 0
    assert svc.poll() == 0                         # idempotent when drained


def test_poll_logical_clock_does_not_self_tick():
    """With the deterministic logical clock, polling must not age the queue
    (a tick-per-poll would turn N no-op polls into a spurious flush)."""
    rng = np.random.default_rng(71)
    with pytest.warns(RuntimeWarning, match="logical clock"):
        svc = _svc(flush_after=5.0)
    svc.append("t", _codes(rng, 8))
    fut = svc.submit("t", rng.integers(0, 8, (WIDTH,)))
    for _ in range(20):                            # >> flush_after ticks
        assert svc.poll() == 0
    assert not fut.done
    # an explicit now= drives the logical-clock deadline instead
    assert svc.poll(now=svc._clock + 5.0) == 1
    assert fut.done


def test_poll_without_deadline_is_noop():
    rng = np.random.default_rng(72)
    svc = _svc()                                   # flush_after=None
    svc.append("t", _codes(rng, 8))
    fut = svc.submit("t", rng.integers(0, 8, (WIDTH,)))
    assert svc.poll() == 0 and not fut.done
    svc.flush()
    assert fut.done


# ---------------------------------------------------------------------------
# cross-request dedup: duplicate rows dispatch once, fan out to all
# ---------------------------------------------------------------------------

def test_dedup_fans_shared_row_out_to_duplicates():
    rng = np.random.default_rng(80)
    svc = _svc()
    codes = _codes(rng, 6)
    svc.append("t", codes, values=list(range(6)))
    futs = [svc.submit("t", codes[2], k=2) for _ in range(5)]
    futs += [svc.submit("t", codes[4], k=2)]
    svc.flush()
    for fut in futs[:5]:
        r = fut.result()
        assert r.hit and r.best_row == 2 and r.value == 2
    assert futs[5].result().value == 4
    s = svc.stats()
    assert s["dedup_hits"] == 4                    # 5 copies -> 1 dispatched
    assert s["dedup_rate"] == pytest.approx(4 / 6)
    # every duplicate still counted as its own lookup
    assert svc.stats("t")["hits"] == 6
    # distinct rids on the fanned-out responses
    assert len({f.result().rid for f in futs}) == 6


def test_dedup_shrinks_the_padding_bucket():
    """9 copies of one query collapse to a 1-wide dispatch: the compiled
    bucket signature is the q=1 bucket, not the q=16 one."""
    rng = np.random.default_rng(81)
    svc = _svc()
    codes = _codes(rng, 4)
    svc.append("t", codes, values=list(range(4)))
    for _ in range(9):
        svc.submit("t", codes[1])
    svc.flush()
    assert svc.stats()["compilations"] == 1
    svc.submit("t", codes[0])                      # a genuine 1-wide flush
    svc.flush()
    assert svc.stats()["compilations"] == 1        # same bucket, cached
    assert svc.stats()["dedup_hits"] == 8


def test_dedup_keys_include_threshold():
    """Identical queries with different thresholds must NOT collapse —
    matched flags differ per request."""
    rng = np.random.default_rng(82)
    svc = _svc()
    codes = _codes(rng, 4)
    svc.append("t", codes, values=list(range(4)))
    q = (codes[0] + 1) % 8                         # misses every row
    d0 = float(np.sum(q[None] != codes, axis=1).min())   # nearest distance
    lo = svc.submit("t", q, k=1, threshold=d0 - 1)
    hi = svc.submit("t", q, k=1, threshold=d0)
    hi2 = svc.submit("t", q, k=1, threshold=d0)
    svc.flush()
    assert not lo.result().matched[0]
    assert hi.result().matched[0] and hi2.result().matched[0]
    assert svc.stats()["dedup_hits"] == 1          # only the exact repeat


# ---------------------------------------------------------------------------
# eviction policies: LRU, TTL, reject — capacity is a hard bound
# ---------------------------------------------------------------------------

def test_lru_evicts_least_recently_hit():
    rng = np.random.default_rng(8)
    svc = _svc(capacity=4)
    codes = _codes(rng, 6)
    svc.append("t", codes[:4], values=[0, 1, 2, 3])
    assert svc.lookup("t", codes[0]).hit           # touch row 0
    assert svc.lookup("t", codes[2]).hit           # touch row 2
    svc.append("t", codes[4:], values=[4, 5])      # overflow by 2
    s = svc.stats("t")
    assert s["rows"] == 4 and s["evicted"] == 2
    # untouched rows 1, 3 were evicted; touched rows and new rows survive
    for i in (0, 2, 4, 5):
        assert svc.lookup("t", codes[i]).value == i
    for i in (1, 3):
        assert not svc.lookup("t", codes[i]).hit
    assert len(svc._tables["t"].values) == svc._tables["t"].n


def test_lru_touch_happens_inside_dispatch():
    """The last-hit column updates on exact hits without any host writeback."""
    rng = np.random.default_rng(9)
    svc = _svc(capacity=8)
    codes = _codes(rng, 3)
    svc.append("t", codes, values=[0, 1, 2])
    before = np.asarray(svc._tables["t"].table.meta[:3, am.META_LAST_HIT])
    svc.lookup("t", codes[1])
    svc.lookup("t", (codes[1] + 1) % 8)            # miss: touches nothing
    after = np.asarray(svc._tables["t"].table.meta[:3, am.META_LAST_HIT])
    assert after[1] > before[1]
    np.testing.assert_array_equal(after[[0, 2]], before[[0, 2]])


def test_ttl_expires_by_insert_time():
    svc = _svc(capacity=8, policy="ttl", ttl=5.0)
    rng = np.random.default_rng(10)
    codes = _codes(rng, 3)
    svc.append("t", codes[0], values=["old"], now=0.0)
    svc.append("t", codes[1], values=["new"], now=4.0)
    assert svc.evict("t", now=7.0) == 1            # only the 0.0 row expired
    assert not svc.lookup("t", codes[0]).hit
    assert svc.lookup("t", codes[1]).value == "new"
    # appends also sweep expired rows
    svc.append("t", codes[2], values=["x"], now=20.0)
    assert svc.stats("t")["rows"] == 1


def test_ttl_overflow_falls_back_to_fifo():
    svc = _svc(capacity=2, policy="ttl", ttl=100.0)
    rng = np.random.default_rng(11)
    codes = _codes(rng, 3)
    for i in range(3):                             # nothing expired yet
        svc.append("t", codes[i], values=[i], now=float(i))
    s = svc.stats("t")
    assert s["rows"] == 2 and s["evicted"] == 1
    assert not svc.lookup("t", codes[0]).hit       # oldest insert went first
    assert svc.lookup("t", codes[2]).hit


def test_logical_clock_rebase_preserves_lru_and_ttl():
    """Near float32's integer limit the clock rebases; ordering survives."""
    from repro.serve import am_service
    rng = np.random.default_rng(20)
    svc = _svc(capacity=4)
    codes = _codes(rng, 6)
    svc.append("t", codes[:4], values=[0, 1, 2, 3])
    svc._clock = am_service._REBASE_TICKS - 2      # force an imminent rebase
    assert svc.lookup("t", codes[0]).hit           # touch 0 (pre-rebase)
    assert svc.lookup("t", codes[2]).hit           # touch 2 (post-rebase)
    assert svc._clock < am_service._REBASE_TICKS / 2
    assert float(np.asarray(svc._tables["t"].table.meta).min()) < 0
    svc.append("t", codes[4:], values=[4, 5])      # overflow by 2
    for i in (0, 2, 4, 5):                         # recency survived rebase
        assert svc.lookup("t", codes[i]).value == i
    for i in (1, 3):
        assert not svc.lookup("t", codes[i]).hit
    # TTL ages also survive a shift: both columns moved together
    svc2 = _svc(capacity=8, policy="ttl", ttl=5.0)
    svc2.append("t", codes[0], values=["a"])
    svc2._clock = am_service._REBASE_TICKS - 1
    svc2.lookup("t", codes[0])                     # ticks across the rebase
    assert svc2.evict("t") == 1                    # age >> ttl still expires


def test_reject_policy_raises_instead_of_evicting():
    svc = _svc(capacity=2, policy="reject")
    rng = np.random.default_rng(12)
    codes = _codes(rng, 3)
    svc.append("t", codes[:2])
    with pytest.raises(TableFullError):
        svc.append("t", codes[2:])
    assert svc.stats("t")["rows"] == 2


def _evict_reference(meta, policy, ttl, now, m, capacity):
    """The rows an append of ``m`` evicts, in the order the policy picks
    them: expired rows (``ttl``) by row, then the overflow by last hit
    (``lru``) or insert time (``ttl``), ties by row."""
    kill = np.zeros(len(meta), bool)
    if policy == "ttl":
        kill = (now - meta[:, am.META_INSERT]) > ttl
    picked = list(np.flatnonzero(kill))
    overflow = len(meta) - kill.sum() + m - capacity
    if overflow > 0 and policy != "reject":
        col = am.META_LAST_HIT if policy == "lru" else am.META_INSERT
        alive = np.flatnonzero(~kill)
        picked += list(alive[np.argsort(meta[alive, col],
                                        kind="stable")][:overflow])
    return [int(i) for i in picked]


#: Rows a final append (at clock 5.0) of ``m`` rows evicts from the table
#: ``_eviction_scenario`` builds: rows 0-2 inserted at 1.0, 3-4 at 2.0, 5 at
#: 4.0; rows 0 and 4 hit at 4.5 and 4.75; capacity 8, ttl 3.5.
EVICTION_CASES = {
    # (policy, fill): (m, rows evicted in pick order, meta readbacks)
    ("reject", "below"): (2, [], 0),
    ("reject", "at"): (3, None, 0),                # TableFullError
    ("lru", "below"): (2, [], 0),
    ("lru", "at"): (3, [1], 1),                    # last hit 1 ties 2
    ("ttl", "below"): (2, [0, 1, 2], 3),           # expiry alone
    ("ttl", "at"): (6, [0, 1, 2, 3], 3),           # + FIFO: insert 3 ties 4
}


def _eviction_scenario(policy, fill, compiles=None, **kw):
    """Build the table of ``EVICTION_CASES``, make its final append, and
    check the evictions, payloads, readback count and the column reader's
    compiles (one, where it reads at all, unless ``compiles`` says)."""
    m, want, readbacks = EVICTION_CASES[policy, fill]
    clock = [0.0]
    svc = AMService(time_fn=lambda: clock[0], **kw)
    svc.create_table("t", width=WIDTH, bits=3, capacity=8, policy=policy,
                     ttl=3.5 if policy == "ttl" else None, backend="ref")
    rng = np.random.default_rng(22)
    codes = _codes(rng, 6 + m)
    assert len(np.unique(codes, axis=0)) == len(codes)
    _meta_column.clear_cache()
    for at, lo, hi in ((1.0, 0, 3), (2.0, 3, 5), (4.0, 5, 6)):
        clock[0] = at
        svc.append("t", codes[lo:hi], values=list(range(lo, hi)))
    for at, row in ((4.5, 0), (4.75, 4)):
        clock[0] = at
        assert svc.lookup("t", codes[row]).value == row
    meta = np.asarray(svc._tables["t"].table.meta)[:6]
    clock[0] = 5.0
    new = list(range(6, 6 + m))
    if want is None:
        with pytest.raises(TableFullError):
            svc.append("t", codes[6:], values=new)
        keep = list(range(6))
    else:
        assert _evict_reference(meta, policy, 3.5, 5.0, m, 8) == want
        svc.append("t", codes[6:], values=new)
        keep = [i for i in range(6) if i not in want] + new
    s = svc.stats("t")
    assert s["rows"] == len(keep) and s["evicted"] == len(want or [])
    assert s["meta_readbacks"] == readbacks
    assert svc.stats()["meta_readbacks"] == readbacks
    # the column reader compiles per layout, whatever row counts read it
    assert _meta_column._cache_size() == (
        min(readbacks, 1) if compiles is None else compiles)
    # survivors keep their order, codes and payloads
    assert svc._tables["t"].values == keep
    np.testing.assert_array_equal(np.asarray(svc.live_table("t").codes),
                                  codes[keep])
    for i in keep:
        assert svc.lookup("t", codes[i]).value == i
    for i in set(range(6 + m)) - set(keep):
        assert not svc.lookup("t", codes[i]).hit
    return svc


@pytest.mark.parametrize("policy,fill", list(EVICTION_CASES))
def test_make_room_reads_meta_only_where_the_policy_can_evict(policy, fill):
    _eviction_scenario(policy, fill)


def test_delete_and_drop_table():
    rng = np.random.default_rng(13)
    svc = _svc()
    codes = _codes(rng, 5)
    svc.append("t", codes, values=list(range(5)))
    assert svc.delete("t", [1, 3]) == 2
    assert svc.lookup("t", codes[4]).value == 4    # payloads track compaction
    assert not svc.lookup("t", codes[1]).hit
    mask = np.zeros(3, bool)
    mask[0] = True
    assert svc.delete("t", mask) == 1              # boolean-mask path
    assert not svc.lookup("t", codes[0]).hit
    svc.drop_table("t")
    with pytest.raises(ValueError):
        svc.lookup("t", codes[0])


# ---------------------------------------------------------------------------
# sharded placement: same service API, mesh-banked search
# ---------------------------------------------------------------------------

def test_sharded_placement_matches_local():
    mesh = jax.make_mesh((min(8, len(jax.devices())),), ("model",))
    rng = np.random.default_rng(14)
    codes = _codes(rng, 11, width=8)
    # merge="tree" forces the hierarchical topology below its auto threshold
    # (mesh width 8 < TREE_MERGE_MIN_BANKS): the service dispatch must stay
    # bitwise-identical to the local service under either merge
    local, sharded = AMService(), AMService(mesh=mesh, merge="tree")
    for svc in (local, sharded):
        svc.create_table("t", width=8, bits=3, capacity=32, policy="lru",
                         backend="pallas")
        svc.append("t", codes, values=list(range(11)))
    queries = [rng.integers(0, 8, (8,)).astype(np.int32) for _ in range(5)]
    queries.append(codes[7])
    fl = [local.submit("t", q, k=4, threshold=3.0) for q in queries]
    fs = [sharded.submit("t", q, k=4, threshold=3.0) for q in queries]
    local.flush(), sharded.flush()
    for a, b in zip(fl, fs):
        ra, rb = a.result(), b.result()
        np.testing.assert_array_equal(ra.indices, rb.indices)
        np.testing.assert_array_equal(ra.distances, rb.distances)
        np.testing.assert_array_equal(ra.matched, rb.matched)
        assert ra.value == rb.value
    assert sharded.stats()["sharded"] and sharded.stats()["readbacks"] == 1
    assert sharded.stats()["merge"] == "tree"
    # eviction works identically over the banked placement
    sharded.append("t", _codes(rng, 25, width=8))
    assert sharded.stats("t")["rows"] <= 32
    # the merge knob is validated at construction, not at dispatch time
    try:
        AMService(merge="mesh")
    except ValueError as e:
        assert "mesh" in str(e)
    else:
        raise AssertionError("AMService accepted an unknown merge strategy")


def test_default_mesh_appends_past_capacity_stay_banked():
    """``jax.make_mesh`` with its default (Explicit) axis types: appends of
    any row count and the LRU compaction past capacity keep the slab banked
    over ``model``, and lookups stay bitwise equal to a local service and
    to the ``ref`` search of the live rows."""
    mesh = jax.make_mesh((len(jax.devices()),), ("model",))
    rng = np.random.default_rng(21)
    local, sharded = AMService(), AMService(mesh=mesh)
    for svc in (local, sharded):
        svc.create_table("t", width=8, bits=3, capacity=24, policy="lru",
                         backend="pallas")
    for m in (5, 7, 3, 9, 11):                     # 35 rows into 24 slots
        codes = _codes(rng, m, width=8)
        local.append("t", codes)
        sharded.append("t", codes)
    slab = sharded._tables["t"].table.codes
    assert slab.sharding.spec[0] == "model" and not slab.is_fully_replicated
    assert sharded.stats("t")["rows"] == 24
    live = sharded.live_table("t")
    queries = np.concatenate([np.asarray(live.codes[::5]),
                              _codes(rng, 4, width=8)])
    want = am.search(live, queries, k=4, backend="ref")
    fl = [local.submit("t", q, k=4) for q in queries]
    fs = [sharded.submit("t", q, k=4) for q in queries]
    for i, (a, b) in enumerate(zip(fl, fs)):
        ra, rb = a.result(), b.result()
        np.testing.assert_array_equal(rb.indices, want.indices[i])
        np.testing.assert_array_equal(rb.distances, want.distances[i])
        np.testing.assert_array_equal(ra.indices, rb.indices)
        np.testing.assert_array_equal(ra.distances, rb.distances)


@pytest.mark.parametrize("policy,compiles", [("lru", 1), ("ttl", 2)])
def test_banked_eviction_matches_reference(policy, compiles):
    """Eviction at capacity over a banked slab: the same rows go, the meta
    column is read once per append that may evict, and the slab stays
    banked.  On a mesh the dispatch hands the touched meta back replicated
    as ``P()``, the slab's own layout is ``P(None, None)``: ``ttl`` reads
    under both (rows 3 and 5 before the lookups, 6 after), ``lru`` only
    after."""
    mesh = jax.make_mesh((len(jax.devices()),), ("model",))
    svc = _eviction_scenario(policy, "at", compiles=compiles, mesh=mesh)
    slab = svc._tables["t"].table.codes
    assert slab.sharding.spec[0] == "model" and not slab.is_fully_replicated


def test_next_pow2():
    assert [_next_pow2(n) for n in (1, 2, 3, 4, 5, 63, 64, 65)] == \
        [1, 2, 4, 4, 8, 64, 64, 128]
