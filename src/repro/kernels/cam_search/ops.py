"""Jitted public wrapper around the CAM-search Pallas kernel.

Handles padding to TPU-aligned block multiples, dtype normalisation, backend
selection (compiled on the TPU, interpreted on the CPU, refused anywhere
else — :func:`repro.kernels.interpret_mode`), and derived outputs
(exact-match flags, top-k / best-row readout).

Distance-unit contract
----------------------
This module backs the ``"pallas"`` backend of :mod:`repro.core.am` and must
honour its unit contract: :func:`mismatch_counts` returns the **exact integer
number of differing symbol positions** between each (query, stored) word pair
— zero iff the words are equal, at most D.  The one-hot Gram formulation
guarantees this bit-precisely (match counts are sums of 0/1 products
accumulated in f32, exact for any D < 2**24), so the ``am`` layer's
``threshold`` and ``EXACT_MATCH_EPS`` semantics hold without slack.  L1
(level-distance) search is realised *above* this wrapper by thermometer
expansion; the kernel itself only ever counts symbol mismatches.

Capability tiers (the ``am`` backend contract comes in two)
-----------------------------------------------------------
* **dense** — ``fn(queries, codes, bits, distance) -> (Q, N)`` distance
  matrix in contract units; the caller extracts top-k with ``lax.top_k``.
  :func:`mismatch_counts` is this module's dense tier.
* **fused** — ``fn(..., k=, valid_rows=) -> ((Q, k) rows, (Q, k) f32
  distances)``: top-k is computed *inside* the kernel's N-block stream, the
  (Q, N) matrix is never materialised in HBM, and rows at index >=
  ``valid_rows`` are masked to +inf in-kernel.  :func:`topk_fused` is this
  module's fused tier.

Tie-break ordering guarantee (both tiers, every backend): results are
ordered by ascending (distance, row index) — among equal distances,
**including +inf masked rows**, the lowest row index wins.  This is the
natural order of ``lax.top_k`` over a dense matrix, the fused kernel's
selection rule, and the order the sharded multi-bank merge in
:mod:`repro.core.am` reproduces; a backend that breaks it will disagree
bitwise with the others and with ``search_sharded``.

Masked (ternary) tier
---------------------
Every helper accepts an optional keyword-only ``care`` plane, (N, D) 0/1
flags aligned with ``table``: positions where ``care == 0`` are don't-care
TCAM cells that never count as mismatches.  An all-ones plane is
bitwise-identical to ``care=None`` on both tiers (same exact integers out of
the kernel; see ``kernel._accumulate``), and ``care=None`` leaves today's
unmasked trace untouched.  :func:`topk_fused` additionally takes
``count_le`` — per-query distance thresholds — and then returns a third
(Q,) int32 array counting live rows within threshold (the multi-match
``match_count``), accumulated inside the same streaming pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.cam_search import kernel as _k


def _pad_to(x: jnp.ndarray, axis: int, multiple: int, value) -> jnp.ndarray:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def mismatch_counts(queries: jnp.ndarray, table: jnp.ndarray, bits: int = 3,
                    interpret: bool | None = None, *,
                    care: jnp.ndarray | None = None) -> jnp.ndarray:
    """(Q, D) queries vs (N, D) stored codes -> (Q, N) int32 mismatch counts.

    Symbols in [0, 2**bits).  Pads Q/N/D up to block multiples; padded D
    positions hold the same sentinel on both sides (always match => no skew)
    and padded rows/queries are sliced away.  An optional ``care`` plane
    (N, D) marks don't-care positions with 0 (never mismatches); its padded
    positions hold 0, so padding stays skew-free on the masked path too.
    """
    interpret = interpret_mode(interpret)
    q = jnp.asarray(queries, jnp.int8)
    t = jnp.asarray(table, jnp.int8)
    qn, d = q.shape
    tn = t.shape[0]

    # Small query batches keep 8-row blocks; table rows are the output's
    # lane axis, so they always pad to 128-row blocks (sliced away below).
    bq = 128 if qn > 64 else 8
    bn = 128
    bd = 512 if d >= 512 else 128

    qp = _pad_to(_pad_to(q, 0, bq, 0), 1, bd, 0)
    tp = _pad_to(_pad_to(t, 0, bn, 0), 1, bd, 0)
    cp = None
    if care is not None:
        cp = _pad_to(_pad_to(jnp.asarray(care, jnp.int8), 0, bn, 0), 1, bd, 0)
    out = _k.cam_search(qp, tp, levels=1 << bits, care=cp, block_q=bq,
                        block_n=bn, block_d=bd, interpret=interpret)
    return out[:qn, :tn]


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def exact_match(queries: jnp.ndarray, table: jnp.ndarray, bits: int = 3,
                interpret: bool | None = None, *,
                care: jnp.ndarray | None = None) -> jnp.ndarray:
    """(Q, N) bool exact word-match flags (the digital CAM output).

    With a ``care`` plane this is the ternary-CAM match line: don't-care
    positions are excluded, so a row matches iff every *cared* position
    agrees (wildcard/prefix matching).
    """
    return mismatch_counts(queries, table, bits, interpret, care=care) == 0


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def best_row(queries: jnp.ndarray, table: jnp.ndarray, bits: int = 3,
             interpret: bool | None = None, *,
             care: jnp.ndarray | None = None) -> jnp.ndarray:
    """(Q,) int32 nearest-row readout (analog ML-discharge ranking)."""
    return jnp.argmin(mismatch_counts(queries, table, bits, interpret,
                                      care=care),
                      axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "bits", "interpret"))
def topk(queries: jnp.ndarray, table: jnp.ndarray, k: int = 1, bits: int = 3,
         interpret: bool | None = None, *,
         care: jnp.ndarray | None = None
         ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """k nearest rows per query: ((Q, k) int32 indices, (Q, k) int32 counts).

    ``jax.lax.top_k`` over the negated mismatch matrix — rows ordered by
    ascending mismatch count, ties broken by lowest row index (the same
    ordering the sharded multi-bank merge in :mod:`repro.core.am`
    reproduces).  ``k`` is clamped to the table size.
    """
    mm = mismatch_counts(queries, table, bits, interpret, care=care)
    neg, idx = jax.lax.top_k(-mm, min(k, table.shape[0]))
    return idx.astype(jnp.int32), -neg


@functools.partial(jax.jit, static_argnames=("k", "bits", "interpret",
                                             "merge_alg"))
def topk_fused(queries: jnp.ndarray, table: jnp.ndarray, k: int = 1,
               bits: int = 3, valid_rows: jnp.ndarray | None = None,
               interpret: bool | None = None, *,
               care: jnp.ndarray | None = None,
               count_le: jnp.ndarray | None = None,
               merge_alg: str = "bitonic"):
    """Streaming top-k: ((Q, k) int32 rows, (Q, k) float32 distances).

    The fused capability tier: one :func:`~repro.kernels.cam_search.kernel.
    cam_search_topk` call whose HBM output is O(Q*k) — the (Q, N) mismatch
    matrix lives and dies in VMEM, block by block.  Bitwise-identical to
    ``lax.top_k`` over :func:`mismatch_counts` (indices, distances, and the
    ascending (distance, row index) tie-break), with masked rows at +inf.

    ``valid_rows`` is an optional (possibly traced) count of live leading
    rows — the fixed-capacity-slab masking happens in-kernel, so serving
    callers pass their fill level without any host-side masking.  ``k`` is
    clamped to the table size.  Padded table rows rank strictly after every
    real row (+inf distance, higher index) and are therefore unreachable
    for k <= N.

    ``care`` is the optional (N, D) don't-care plane (module docstring).
    ``count_le`` — a per-query distance threshold, scalar or (Q,)/(Q, 1) —
    switches on the in-kernel multi-match counter: the return value becomes
    a 3-tuple whose third element is (Q,) int32, the number of live rows at
    distance <= threshold per query.  ``merge_alg`` selects the in-kernel
    per-block merge network (``"bitonic"``, the O(log^2 bn + log k) default, or
    the original ``"argmin"`` k-round selection — bitwise-identical, kept
    for benchmarking; see ``kernel.MERGE_ALGS``).
    """
    interpret = interpret_mode(interpret)
    q = jnp.asarray(queries, jnp.int8)
    t = jnp.asarray(table, jnp.int8)
    qn, d = q.shape
    tn = t.shape[0]
    k = min(k, tn)

    # The merge network works on whole 128-lane candidate blocks, so the
    # table always pads to 128-row blocks here (padded rows sit at +inf).
    bq = 128 if qn > 64 else 8
    bn = 128
    bd = 512 if d >= 512 else 128

    qp = _pad_to(_pad_to(q, 0, bq, 0), 1, bd, 0)
    tp = _pad_to(_pad_to(t, 0, bn, 0), 1, bd, 0)
    cp = None
    if care is not None:
        cp = _pad_to(_pad_to(jnp.asarray(care, jnp.int8), 0, bn, 0), 1, bd, 0)
    thr = None
    if count_le is not None:
        thr = jnp.broadcast_to(
            jnp.asarray(count_le, jnp.float32).reshape(-1, 1), (qn, 1))
        thr = _pad_to(thr, 0, bq, 0.0)
    vr = jnp.asarray(tn if valid_rows is None else valid_rows, jnp.int32)
    vr = jnp.minimum(vr, tn)           # padded rows are never live
    out = _k.cam_search_topk(qp, tp, vr, levels=1 << bits, k=k, care=cp,
                             count_le=thr, block_q=bq, block_n=bn,
                             block_d=bd, interpret=interpret,
                             merge_alg=merge_alg)
    if count_le is None:
        idx, dist = out
        return idx[:qn], dist[:qn]
    idx, dist, cnt = out
    return idx[:qn], dist[:qn], cnt[:qn, 0]
