"""The numpy reference against literal definitions and the served path.

CPU, tiny sizes: the Pallas kernel runs interpreted.
"""

import time

import numpy as np
import pytest

from onchip import reference, ycsb


def literal(queries, codes, distance):
    q = queries.astype(np.int64)[:, None, :]
    t = codes.astype(np.int64)[None, :, :]
    if distance == "l1":
        return np.abs(q - t).sum(-1)
    return (q != t).sum(-1)


def literal_topk(queries, codes, k, distance):
    d = literal(queries, codes, distance)
    idx, dist = [], []
    for row in d:
        order = np.lexsort((np.arange(len(row)), row))[:k]
        pad = k - len(order)
        idx.append(np.concatenate([order, np.full(pad, -1)]))
        dist.append(np.concatenate([row[order].astype(np.float32),
                                    np.full(pad, np.inf, np.float32)]))
    return np.array(idx), np.array(dist, np.float32)


@pytest.mark.parametrize("distance,bits,width", [
    ("hamming", 3, 22), ("hamming", 3, 5), ("hamming", 2, 40),
    ("l1", 3, 16), ("l1", 2, 9)])
def test_distances_match_their_definition(distance, bits, width):
    rng = np.random.default_rng(width)
    q = rng.integers(0, 1 << bits, (7, width)).astype(np.int8)
    t = rng.integers(0, 1 << bits, (50, width)).astype(np.int8)
    got = reference.DISTANCES[distance](q, t, bits)
    np.testing.assert_array_equal(got, literal(q, t, distance))


@pytest.mark.parametrize("distance", ["hamming", "l1"])
@pytest.mark.parametrize("k", [1, 4, 40])
def test_topk_ties_go_to_the_lowest_row(distance, k):
    rng = np.random.default_rng(k)
    # few levels and cells: most distances tie
    t = rng.integers(0, 2, (33, 4)).astype(np.int8)
    q = rng.integers(0, 2, (6, 4)).astype(np.int8)
    idx, dist = reference.topk(q, t, k=k, distance=distance, bits=1,
                               block=5)
    want_idx, want_dist = literal_topk(q, t, k, distance)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_array_equal(dist, want_dist)


def test_limit_hides_rows_a_lookup_could_not_see():
    t = np.array([[1, 1], [0, 0], [0, 0]], np.int8)
    q = np.array([[0, 0], [0, 0]], np.int8)
    idx, dist = reference.topk(q, t, k=2, distance="hamming", bits=1,
                               limit=np.array([1, 3]))
    np.testing.assert_array_equal(idx, [[0, -1], [1, 2]])
    np.testing.assert_array_equal(dist, [[2, np.inf], [0, 0]])


def _service(codes, distance, bits, capacity, values=None):
    from repro.serve import AMService
    svc = AMService(time_fn=time.monotonic, max_batch=8)
    svc.create_table("t", width=codes.shape[1], bits=bits, distance=distance,
                     capacity=capacity, policy="reject", backend="pallas")
    svc.append("t", codes.astype(np.int32), values)
    return svc


def _served(svc, queries, k):
    futs = [svc.submit("t", q.astype(np.int32), k=k) for q in queries]
    svc.flush()
    return [f.result() for f in futs]


@pytest.mark.parametrize("distance,bits,width,k", [
    ("l1", 3, 16, 10), ("hamming", 3, 22, 1), ("hamming", 3, 22, 5)])
def test_reference_agrees_with_the_served_path(distance, bits, width, k):
    rng = np.random.default_rng(k + width)
    codes = rng.integers(0, 1 << bits, (300, width)).astype(np.int8)
    codes[100:110] = codes[5]               # duplicates: the tie-break
    queries = np.concatenate([codes[[5, 7, 299]],
                              rng.integers(0, 1 << bits, (5, width))
                              ]).astype(np.int8)
    queries = np.concatenate([queries, queries[:2]])   # dedup fan-out
    values = [f"rec{i}" for i in range(len(codes))]
    svc = _service(codes, distance, bits, 512, values)
    got = _served(svc, queries, k)
    idx, dist = reference.topk(queries, codes, k=k, distance=distance,
                               bits=bits)
    for r, i, d in zip(got, idx, dist):
        assert reference.differs(r, reference.expected(i, d, values)) is None


def test_read_your_insert_matches_the_served_path():
    cells = ycsb.fnvhash64(np.arange(40))
    from onchip import datagen
    pool = datagen.key_cells(cells, 22, 3)
    values = [f"rec{i}" for i in range(40)]
    svc = _service(pool[:30], "hamming", 3, 64, values[:30])
    for r in range(30, 40):
        svc.append("t", pool[r:r + 1].astype(np.int32), [values[r]])
        got = _served(svc, pool[[r, r - 1, 3]], 1)
        idx, dist = reference.topk(pool[[r, r - 1, 3]], pool, k=1,
                                   distance="hamming", bits=3,
                                   limit=np.full(3, r + 1))
        for g, i, d in zip(got, idx, dist):
            want = reference.expected(i, d, values)
            assert reference.differs(g, want) is None
            assert g.value == values[int(i[0])]


def test_controls_break_their_guarantee():
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 8, (400, 16)).astype(np.int8)
    q = codes[rng.integers(0, 400, 30)]
    q = np.clip(q + rng.integers(-1, 2, q.shape), 0, 7).astype(np.int8)
    args = dict(k=10, distance="l1", bits=3)
    want = reference.topk(q, codes, **args)
    for name, fn in reference.CONTROLS.items():
        got = fn(q, codes, loaded=200, limit=np.full(len(q), 400), **args)
        bad = sum(not (np.array_equal(gi, wi) and np.array_equal(gd, wd))
                  for gi, gd, wi, wd in zip(*got, *want))
        assert bad > len(q) // 3, name


def test_fnvhash64_is_ycsbs():
    # Utils.fnvhash64 of 0, 1 and 2 as YCSB's Java computes them
    np.testing.assert_array_equal(
        ycsb.fnvhash64([0, 1, 2]),
        [6284781860667377211, 8517097267634966620, 1820151046732198393])


def test_zipfian_popularity_falls_with_rank():
    z = ycsb.Zipfian(1000)
    r = z.ranks(np.random.default_rng(1).random(200_000))
    counts = np.bincount(r, minlength=1000)
    assert r.min() == 0 and r.max() < 1000
    # rank 0 against rank 9 follows 1/i**0.99 within sampling noise
    assert 8.0 < counts[0] / counts[9] < 12.0


def test_latest_favours_the_newest_record():
    z = ycsb.Zipfian(100)
    u = np.random.default_rng(2).random(5000)
    picks = [ycsb.latest(x, z, 100) for x in u]
    assert max(picks) == 99 and min(picks) >= 0
    assert np.mean(np.array(picks) == 99) > 0.1
    assert ycsb.latest(0.0, z, 120) == 119      # grows with inserts
