"""Peaks of the chip and the work a kernel call must do, from shapes alone.

The peaks live in ``peaks.json`` beside this file, keyed by JAX's
``device_kind``, with their source.  A device that is not in the table is an
error, never a default.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peak table's entry for ``device_kind``; raises for an unknown one."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(table)}")
    return table[device_kind]


def topk_least_time(q: int, n: int, d: int, bits: int, k: int,
                    peak: dict) -> tuple[float, str]:
    """Least seconds a top-k search of ``q`` queries over ``n`` rows of ``d``
    cells at ``bits`` bits can take, and which term binds.

    Counted from the configuration's own cells, before any expansion the
    program makes: one compare-and-add per query, row and cell
    (``2 q n d`` operations at the int8 peak), and the table's cells at
    ``bits`` bits, one byte per query cell and an int32 row and a float32
    distance per answer (at HBM bandwidth).
    """
    t_ops = 2.0 * q * n * d / peak["int8_op_per_s"]
    t_bytes = (n * d * bits / 8 + q * d + q * k * 8) / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
