"""Table contents made from a seed, in bulk with numpy.

A configuration's ``kind`` picks how its table is made:

* ``vectors`` — ANN retrieval.  ``rows`` base vectors of ``width`` cells at
  ``bits`` bits, drawn around ``clusters`` centres (levels skewed towards 0,
  as SIFT's gradient histograms are), and ``query_set`` queries, each a base
  row with a few cells moved by one level.
* ``keyed`` — a YCSB usertable.  Record ``i``'s key is YCSB's
  ``fnvhash64(i)`` (``insertorder=hashed``), held as ``width`` cells of
  ``bits`` bits, low cells first; its value is ``fieldcount`` x
  ``fieldlength`` random bytes.  Records past ``recordcount`` are the ones
  a run may insert, up to the table's capacity.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from onchip import ycsb


@dataclasses.dataclass
class TableData:
    """What a run loads, and what its traffic draws from."""

    codes: np.ndarray                 # (rows, width) int8: the bulk load
    queries: np.ndarray | None = None  # (query_set, width) int8 (vectors)
    pool: np.ndarray | None = None     # (capacity, width) int8 (keyed)
    records: np.ndarray | None = None  # (capacity, record bytes) uint8

    def values(self, start: int, stop: int) -> list:
        """Payloads of records ``start..stop`` (views, no copies)."""
        return list(self.records[start:stop])


def key_cells(keys: np.ndarray, width: int, bits: int) -> np.ndarray:
    """64-bit keys as ``width`` cells of ``bits`` bits, low cells first."""
    keys = np.asarray(keys, np.int64).astype(np.uint64)
    shifts = (np.arange(width, dtype=np.uint64) * np.uint64(bits))
    mask = np.uint64((1 << bits) - 1)
    return ((keys[:, None] >> shifts[None, :]) & mask).astype(np.int8)


def _nudge(rng, shape) -> np.ndarray:
    """Cell moves in {-1, 0, +1} with probabilities 0.2, 0.6, 0.2."""
    lut = np.array([-1, 0, 0, 0, 1], np.int8)
    return lut[rng.integers(0, 5, shape, dtype=np.int8)]


def vectors(cfg: dict, rng) -> TableData:
    n, d, levels = cfg["rows"], cfg["width"], 1 << cfg["bits"]
    p = 0.6 ** np.arange(levels)
    centres = rng.choice(levels, size=(cfg["clusters"], d),
                         p=p / p.sum()).astype(np.int8)
    codes = centres[rng.integers(0, cfg["clusters"], n)]
    codes += _nudge(rng, (n, d))
    np.clip(codes, 0, levels - 1, out=codes)
    queries = codes[rng.integers(0, n, cfg["query_set"])]
    queries += _nudge(rng, queries.shape)
    np.clip(queries, 0, levels - 1, out=queries)
    return TableData(codes=codes, queries=queries)


def keyed(cfg: dict, rng) -> TableData:
    cap, n = cfg["capacity"], cfg["recordcount"]
    pool = key_cells(ycsb.fnvhash64(np.arange(cap)), cfg["width"],
                     cfg["bits"])
    size = cfg["fieldcount"] * cfg["fieldlength"]
    records = np.frombuffer(rng.bytes(cap * size), np.uint8).reshape(cap,
                                                                    size)
    return TableData(codes=pool[:n], pool=pool, records=records)


BUILDERS = {"vectors": vectors, "keyed": keyed}


def build(cfg: dict, seed: int) -> TableData:
    return BUILDERS[cfg["kind"]](cfg, np.random.default_rng(seed))
