"""YCSB's key and request generators, vectorised over numpy.

Follows the Yahoo! Cloud Serving Benchmark's own generators
(``site.ycsb.generator``): ``ZipfianGenerator`` (Gray et al., "Quickly
generating billion-record synthetic databases", SIGMOD 1994),
``ScrambledZipfianGenerator`` (workloads a-c) and ``SkewedLatestGenerator``
(workload d), and ``Utils.fnvhash64``, which also turns a record number into
its key under YCSB's default ``insertorder=hashed``.  Every draw takes its
uniforms from the caller, so a seed fixes the requests.
"""

from __future__ import annotations

import numpy as np

#: YCSB's ``ZipfianGenerator.ZIPFIAN_CONSTANT``.
ZIPFIAN_CONSTANT = 0.99
#: ``ScrambledZipfianGenerator.ITEM_COUNT`` and its precomputed ``ZETAN``.
SCRAMBLED_ITEM_COUNT = 10_000_000_000
SCRAMBLED_ZETAN = 26.46902820178302

_FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
_FNV_PRIME_64 = np.uint64(1099511628211)


def fnvhash64(values) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of each value: FNV-1a over its 8 low bytes,
    made non-negative as ``Math.abs`` does (values fit 63 bits)."""
    val = np.asarray(values, np.int64).astype(np.uint64)
    h = np.full(val.shape, _FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= val & np.uint64(0xFF)
            val = val >> np.uint64(8)
            h *= _FNV_PRIME_64
    signed = h.view(np.int64)
    return np.where(signed < 0, -signed, signed).astype(np.int64)


def zeta(n: int, theta: float = ZIPFIAN_CONSTANT) -> float:
    """``sum_{i=1..n} 1 / i**theta``."""
    return float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -theta))


class Zipfian:
    """``ZipfianGenerator`` over ``[0, items)``: rank 0 is the most popular.

    ``grow`` extends the item count as records are inserted, updating zeta
    incrementally as YCSB's ``nextLong(itemcount)`` does.
    """

    def __init__(self, items: int, theta: float = ZIPFIAN_CONSTANT,
                 zetan: float | None = None):
        self.theta = theta
        self.alpha = 1.0 / (1.0 - theta)
        self.zeta2 = zeta(2, theta)
        self.items = items
        self.zetan = zeta(items, theta) if zetan is None else zetan
        self._eta()

    def _eta(self) -> None:
        self.eta = ((1.0 - (2.0 / self.items) ** (1.0 - self.theta))
                    / (1.0 - self.zeta2 / self.zetan))

    def grow(self, items: int) -> None:
        if items > self.items:
            extra = np.arange(self.items + 1, items + 1, dtype=np.float64)
            self.zetan += float(np.sum(extra ** -self.theta))
            self.items = items
            self._eta()

    def ranks(self, u) -> np.ndarray:
        """Ranks for uniforms ``u`` in [0, 1)."""
        u = np.asarray(u, np.float64)
        uz = u * self.zetan
        far = (self.items * (self.eta * u - self.eta + 1.0) ** self.alpha)
        r = np.where(uz < 1.0, 0,
                     np.where(uz < 1.0 + 0.5 ** self.theta, 1,
                              far.astype(np.int64)))
        return np.minimum(r, self.items - 1).astype(np.int64)


def scrambled_zipfian(u, items: int) -> np.ndarray:
    """``ScrambledZipfianGenerator(0, items - 1)``: Zipfian popularity with
    the popular items spread over the key space by ``fnvhash64``."""
    z = Zipfian(SCRAMBLED_ITEM_COUNT, zetan=SCRAMBLED_ZETAN)
    return fnvhash64(z.ranks(u)) % items


def latest(u: float, zipf: Zipfian, count: int) -> int:
    """``SkewedLatestGenerator``: the newest of ``count`` records is the most
    popular.  ``zipf`` is grown to ``count`` items first."""
    zipf.grow(count)
    return count - 1 - int(zipf.ranks(u))
