"""Plain numpy reference of the CAM search contract, and its controls.

A lookup of ``query`` against rows ``codes`` (each ``width`` cells of ``bits``
bits) returns the ``k`` rows nearest by the table's distance, ascending by
(distance, row): among equal distances the lowest row wins.  Fewer than
``k`` rows give index -1 and distance +inf in the surplus slots.  ``exact``
flags distance 0, and with no threshold ``matched`` equals ``exact``.  A
keyed table's response carries the record of its best row on an exact hit.

* Hamming distance counts the cells that differ.  Rows and queries are
  packed into 64-bit words, ``64 // bits`` cells per word; XOR, fold each
  cell's bits onto its lowest bit, and count the set bits.
* L1 distance sums ``|q_d - t_d|`` over cells.  For integer levels
  ``|a - b| = a + b - 2 min(a, b)`` and ``min(a, b) = sum_{r>=1} [a>=r][b>=r]``,
  so a block of rows is one float32 matrix product of 0/1 level indicators
  (exact: every sum stays below 2**24).

Rows are scanned in blocks, so a reference over 10^6 rows fits in host memory.

Controls put in the program's place break one guarantee that a
configuration states (``CONTROLS``); a sound comparison has to fail them.
"""

from __future__ import annotations

import numpy as np

#: Rows per block of a scan.
BLOCK_ROWS = 1 << 16
#: Rows are numbered below 2**32, so (distance, row) packs into one int64.
_ROW_BITS = 32


def _pack(codes: np.ndarray, bits: int) -> np.ndarray:
    """(n, width) cells -> (n, words) uint64, ``64 // bits`` cells a word."""
    per = 64 // bits
    n, width = codes.shape
    words = -(-width // per)
    out = np.zeros((n, words), np.uint64)
    for c in range(width):
        w, j = divmod(c, per)
        out[:, w] |= codes[:, c].astype(np.uint64) << np.uint64(bits * j)
    return out


def _low_bits(bits: int) -> np.uint64:
    per = 64 // bits
    return np.uint64(sum(1 << (bits * j) for j in range(per)))


def hamming(queries: np.ndarray, codes: np.ndarray, bits: int) -> np.ndarray:
    """(Q, n) int64 count of differing cells."""
    qp, tp = _pack(queries, bits), _pack(codes, bits)
    low = _low_bits(bits)
    out = np.zeros((len(qp), len(tp)), np.int64)
    for w in range(qp.shape[1]):
        x = qp[:, w, None] ^ tp[None, :, w]
        y = x
        for s in range(1, bits):
            y = y | (x >> np.uint64(s))
        out += np.bitwise_count(y & low)
    return out


def _levels(codes: np.ndarray, bits: int) -> np.ndarray:
    """(n, width) levels -> (n, width * (2**bits - 1)) float32 ``[c >= r]``."""
    rungs = np.arange(1, 1 << bits, dtype=np.int8)
    out = codes[:, :, None] >= rungs
    return out.reshape(len(codes), -1).astype(np.float32)


def l1(queries: np.ndarray, codes: np.ndarray, bits: int) -> np.ndarray:
    """(Q, n) int64 sum of absolute level differences."""
    both = _levels(queries, bits) @ _levels(codes, bits).T
    qs = queries.astype(np.int64).sum(1)
    ts = codes.astype(np.int64).sum(1)
    return qs[:, None] + ts[None, :] - 2 * both.astype(np.int64)


DISTANCES = {"hamming": hamming, "l1": l1}


def topk(queries, codes, *, k: int, distance: str, bits: int,
         rows=None, limit=None, block: int = BLOCK_ROWS):
    """((Q, k) int64 rows, (Q, k) float32 distances), nearest first.

    ``rows`` gives each of ``codes``' rows its number (default
    ``arange(n)``): a control that holds only some rows still answers in
    the table's numbering.  ``limit`` (one per query) leaves out the rows
    numbered at or above it: the rows a lookup could not yet see.
    """
    queries = np.asarray(queries)
    codes = np.asarray(codes)
    n = len(codes)
    rows = np.arange(n) if rows is None else np.asarray(rows, np.int64)
    dist_fn = DISTANCES[distance]
    never = np.iinfo(np.int64).max
    best = np.full((len(queries), k), never, np.int64)
    for s in range(0, n, block):
        d = dist_fn(queries, codes[s:s + block], bits)
        keys = (d << _ROW_BITS) | rows[None, s:s + block]
        if limit is not None:
            hidden = rows[None, s:s + block] >= np.asarray(limit)[:, None]
            keys = np.where(hidden, never, keys)
        cand = np.concatenate([best, keys], axis=1)
        if cand.shape[1] > k:
            cand = np.partition(cand, k - 1, axis=1)[:, :k]
        best = np.sort(cand, axis=1)
    empty = best == never
    idx = np.where(empty, -1, best & ((1 << _ROW_BITS) - 1))
    dist = np.where(empty, np.inf, best >> _ROW_BITS).astype(np.float32)
    return idx, dist


def expected(idx: np.ndarray, dist: np.ndarray, values=None) -> dict:
    """The response fields one lookup's reference row gives."""
    exact = dist < 0.5
    value = None
    if values is not None and exact[0]:
        value = values[int(idx[0])]
    return {"indices": idx.astype(np.int32), "distances": dist,
            "exact": exact, "matched": exact, "value": value}


def differs(resp, want: dict) -> str | None:
    """The first field in which a response departs from ``want``."""
    if resp is None:
        return "unanswered"
    for f in ("indices", "distances", "exact", "matched"):
        got = np.asarray(getattr(resp, f))
        exp = np.asarray(want[f])
        if got.shape != exp.shape or not np.array_equal(got, exp):
            return f
    gv, wv = resp.value, want["value"]
    if (gv is None) != (wv is None):
        return "value"
    if gv is not None and not np.array_equal(np.asarray(gv), wv):
        return "value"
    return None


# -- controls -----------------------------------------------------------------


def control_coarse_cells(queries, codes, *, k, distance, bits, limit=None,
                         **_):
    """``exact_top_k`` broken by one bit less per cell: rows chosen over
    ``bits - 1``-bit cells (the nearest precision below the stated one),
    reported with their true distances, the control's best case."""
    idx, _ = topk(queries >> 1, codes >> 1, k=k, distance=distance,
                  bits=bits - 1, limit=limit)
    return idx, _true_distances(queries, codes, idx, distance, bits)


def control_half_capacity(queries, codes, *, k, distance, bits, limit=None,
                          **_):
    """``no_eviction`` broken: a table of half the capacity that evicted
    its oldest half, answering in the full table's row numbering."""
    half = len(codes) // 2
    return topk(queries, codes[half:], k=k, distance=distance, bits=bits,
                rows=np.arange(half, len(codes)), limit=limit)


def control_stale_reads(queries, codes, *, k, distance, bits, loaded, **_):
    """``read_your_insert`` broken: every read answered from the table as
    it stood when the window opened (``loaded`` rows), as if inserts were
    acknowledged before they were visible."""
    return topk(queries, codes[:loaded], k=k, distance=distance, bits=bits)


def _true_distances(queries, codes, idx, distance, bits):
    dist_fn = DISTANCES[distance]
    out = np.full(idx.shape, np.inf, np.float32)
    for i, row in enumerate(idx):
        ok = row >= 0
        out[i, ok] = dist_fn(queries[i:i + 1], codes[row[ok]], bits)[0]
    return out


#: The control for each guarantee a configuration may state.
CONTROLS = {
    "exact_top_k": control_coarse_cells,
    "no_eviction": control_half_capacity,
    "read_your_insert": control_stale_reads,
}
