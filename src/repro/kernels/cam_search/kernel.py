"""Pallas TPU kernel: multi-bit CAM associative search as MXU Gram matmuls.

TPU adaptation of the SEE-MCAM search (DESIGN.md §2).  The CAM computes, for a
query word q and a stored word t, the number of *matching* multi-bit cells.
Bit-serial/analog comparison does not map to a systolic array, but the one-hot
reformulation does:

    #matches(q, t) = sum_d sum_m 1[q_d = m] * 1[t_d = m]
                   = sum_m  onehot_m(q) . onehot_m(t)

i.e. M = 2**bits rank-D Gram products — dense (bq x bd) @ (bd x bn) matmuls
that run on the **MXU** at bf16 throughput, instead of O(D) int compares per
(q, t) pair on the VPU.  Mismatch count = D - #matches, which is exactly the
analog ML-discharge ranking of the paper's array.

Tiling: grid (Q/bq, N/bn, D/bd); the D axis is innermost so each (i, j) output
block accumulates match counts in a VMEM f32 scratch across D steps.  Blocks
default to (bq, bn, bd) = (128, 128, 512): VMEM = 2*(128*512) int8 inputs
+ 128*128 f32 acc + M bf16 one-hot temporaries ~= 0.7 MB << 16 MB v5e VMEM,
and every matmul dimension is a multiple of the 128-lane MXU tiles.

Two kernels share that tiling:

* :func:`cam_search` — the dense tier: writes the full (Q, N) mismatch
  matrix to HBM (callers run their own ``lax.top_k``).
* :func:`cam_search_topk` — the fused/streaming tier: the same grid with the
  N axis as the streaming (inner-of-Q) loop; each N block's distances are
  folded into a running per-query top-k held in a (bq, >= k) VMEM scratch and
  the (bq, bn) distance block never leaves VMEM, so HBM output drops from
  O(Q*N) to O(Q*k).  A prefetched ``valid_rows`` scalar masks dead slab
  rows in-kernel (distance +inf), and ties are broken by lowest global row
  index — bitwise the ordering of ``lax.top_k`` over the dense matrix.
  The per-block fold is an in-register **bitonic merge network**
  (:func:`_bitonic_topk_merge`): O(log^2 bn + log k) compare-exchange
  stages built from lane rotations and selects only — no ``sort``/``top_k``
  primitives, no reversal, no lane-splitting reshape, all of which Mosaic
  refuses — which is what lets the fused tier reach k = 256
  (``am.FUSED_K_MAX``) instead of the k = 64 the original k-round argmin
  selection (kept as ``merge_alg="argmin"``) could afford.

Both kernels optionally take a per-row **care plane** (ternary/don't-care
cells, the FeCAM TCAM mode): masked search accumulates mismatches directly as
``sum_m onehot_m(q) . (care & 1[t != m])`` — one extra AND on the stored-side
one-hot — which for an all-ones plane reproduces the unmasked integers
bit-for-bit (see :func:`_accumulate`).  The streaming kernel additionally
offers an in-kernel per-query **threshold count** (multi-match
``match_count``) folded into the same N-block pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _accumulate(q, t, c, acc, levels: int):
    """One D-block of Gram accumulation; ``c`` selects the ternary variant.

    Unmasked (``c is None``): accumulates *match* counts, the original
    one-hot reformulation (the caller finalises ``D - acc``).  Masked:
    accumulates *mismatch* counts directly — per level m the stored-side
    one-hot becomes ``(t != m) & care``, i.e. the paper's popcount reduction
    with one extra AND against the don't-care plane:

        sum_m 1[q = m] * (care * 1[t != m]) = care * 1[q != t]

    for any in-range q.  An all-ones care plane therefore yields exactly
    ``1[q != t]`` summed over D — the same integers the unmasked path's
    ``D - #matches`` finalisation produces, so all-care masked search is
    bitwise-identical to unmasked search while sharing none of its trace.

    Symbols and care flags travel as int8 but are compared as int32: the
    TPU vector unit has no int8 compare.
    """
    q = q.astype(jnp.int32)
    t = t.astype(jnp.int32)
    care = None if c is None else (c.astype(jnp.int32) != 0)
    for m in range(levels):
        a = (q == m).astype(jnp.bfloat16)
        if care is None:
            b = (t == m).astype(jnp.bfloat16)
        else:
            b = ((t != m) & care).astype(jnp.bfloat16)
        acc = acc + jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return acc


def _cam_search_kernel(*refs, levels: int, d_total: int, nk: int,
                       masked: bool):
    it = iter(refs)
    q_ref, t_ref = next(it), next(it)
    c_ref = next(it) if masked else None
    out_ref, acc_ref = next(it), next(it)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]  # (bq, bd) int8 symbols
    t = t_ref[...]  # (bn, bd) int8 symbols
    c = None if c_ref is None else c_ref[...]  # (bn, bd) int8 care flags
    acc_ref[...] = _accumulate(q, t, c, acc_ref[...], levels)

    @pl.when(k == nk - 1)
    def _finalize():
        acc = acc_ref[...]
        out = acc if masked else jnp.float32(d_total) - acc
        out_ref[...] = out.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("levels", "block_q", "block_n",
                                             "block_d", "interpret"))
def cam_search(queries: jnp.ndarray, table: jnp.ndarray, *, levels: int,
               care: jnp.ndarray | None = None, block_q: int = 128,
               block_n: int = 128, block_d: int = 512,
               interpret: bool = False) -> jnp.ndarray:
    """Mismatch-count matrix between ``queries`` (Q, D) and ``table`` (N, D).

    Inputs are int8 symbols in [0, levels); Q, N, D must be multiples of the
    block sizes (the ops wrapper pads).  Returns (Q, N) int32.

    ``care`` is an optional (N, D) int8 don't-care plane tiled like
    ``table``: positions where ``care == 0`` never count as mismatches
    (ternary CAM cells).  All-care is bitwise-identical to ``care=None``
    (see :func:`_accumulate`); the unmasked trace is unchanged.
    """
    qn, d = queries.shape
    tn, d2 = table.shape
    assert d == d2, (d, d2)
    assert qn % block_q == 0 and tn % block_n == 0 and d % block_d == 0, (
        (qn, tn, d), (block_q, block_n, block_d))
    masked = care is not None
    if masked:
        assert care.shape == table.shape, (care.shape, table.shape)
    nk = d // block_d

    kernel = functools.partial(_cam_search_kernel, levels=levels, d_total=d,
                               nk=nk, masked=masked)
    in_specs = [
        pl.BlockSpec((block_q, block_d), lambda i, j, k: (i, k)),
        pl.BlockSpec((block_n, block_d), lambda i, j, k: (j, k)),
    ]
    operands = [queries, table]
    if masked:
        in_specs.append(pl.BlockSpec((block_n, block_d),
                                     lambda i, j, k: (j, k)))
        operands.append(care)
    return pl.pallas_call(
        kernel,
        grid=(qn // block_q, tn // block_n, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, tn), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_q, block_n), jnp.float32)],
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Fused/streaming top-k: O(Q*k) HBM output instead of O(Q*N)
# ---------------------------------------------------------------------------

#: int32 sentinel for "no row" slots in the running top-k; larger than any
#: real row index, so the lexicographic (distance, index) tie-break always
#: prefers a real candidate over an unfilled slot.  (A plain int — jnp
#: scalars would be captured as constants by the kernel tracer.)
_NO_ROW = 2**31 - 1


#: Merge networks ``cam_search_topk`` can fold candidates with.  The default
#: ``"bitonic"`` is O(log^2 bn + log k) compare-exchange stages per block;
#: ``"argmin"`` is the original k-sequential-round selection, kept callable
#: as the semantic oracle and the benchmark baseline
#: (``benchmarks/bench_am_topk.py`` k-sweep).
MERGE_ALGS = ("bitonic", "argmin")


def _topk_merge(best_d, best_i, cand_d, cand_i, k: int):
    """Fold (bq, bn) candidates into the sorted (bq, k) running top-k.

    The ``"argmin"`` merge network: selection is k rounds of lexicographic
    argmin over (distance, row index) — the minimum distance is extracted
    first, and among equal distances the lowest row index wins — including
    +inf ties, which is exactly how ``lax.top_k`` over a dense masked
    matrix orders dead rows.  Built from min/where/iota only (no
    sort/top_k primitives), so it lowers on the VPU.  O(k*(k+bn)) vector
    ops per block — the historical ceiling that capped the fused tier at
    k <= 64; it survives as the bitwise oracle for
    :func:`_bitonic_topk_merge` and the benchmark baseline.
    """
    comb_d = jnp.concatenate([best_d, cand_d], axis=1)
    comb_i = jnp.concatenate([best_i, cand_i], axis=1)
    out_d, out_i = [], []
    for _ in range(k):
        d_t = jnp.min(comb_d, axis=1, keepdims=True)            # (bq, 1)
        i_t = jnp.min(jnp.where(comb_d == d_t, comb_i, jnp.int32(_NO_ROW)),
                      axis=1, keepdims=True)                    # (bq, 1)
        taken = (comb_d == d_t) & (comb_i == i_t)
        comb_d = jnp.where(taken, jnp.inf, comb_d)
        comb_i = jnp.where(taken, jnp.int32(_NO_ROW), comb_i)
        out_d.append(d_t)
        out_i.append(i_t)
    return jnp.concatenate(out_d, axis=1), jnp.concatenate(out_i, axis=1)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _lex_lt(d_a, i_a, d_b, i_b):
    """Strict two-key less-than: (d_a, i_a) < (d_b, i_b) lexicographically.

    The Contract-2 order — ascending distance, ascending row index among
    equal distances (+inf masked rows included; the +inf/`_NO_ROW` sentinel
    pair is the lexicographic maximum, so sentinels can never displace a
    genuine candidate).
    """
    return (d_a < d_b) | ((d_a == d_b) & (i_a < i_b))


def _lane_bit(lane, j: int):
    """``(lane & j) != 0`` as int32 0/1, for a power-of-two ``j``."""
    return (lane >> (j.bit_length() - 1)) & 1


def _compare_exchange(d, i, lane, j: int, dir_bit):
    """One bitonic compare-exchange step at pair distance ``j``.

    Element ``x`` meets its partner ``x ^ j`` through two lane rotations
    (``x + j`` and ``x - j``) and a select on ``lane & j`` — no gathers, no
    reshape, so the step is a handful of rotate/compare/select ops the VPU
    lowers directly.  ``dir_bit`` is an int32 0/1 array (or 0) marking the
    lanes whose pair block sorts descending.  Elements are (distance,
    row-index) pairs under the :func:`_lex_lt` total order; equal pairs are
    never swapped either way, so the network is deterministic and
    order-stable on sentinel plateaus.
    """
    ln = d.shape[-1]
    hi = _lane_bit(lane, j)
    up = hi == 0                              # partner sits at x + j
    p_d = jnp.where(up, pltpu.roll(d, ln - j, 1), pltpu.roll(d, j, 1))
    p_i = jnp.where(up, pltpu.roll(i, ln - j, 1), pltpu.roll(i, j, 1))
    keep_min = (hi ^ dir_bit) == 0
    take = ((keep_min & _lex_lt(p_d, p_i, d, i))
            | (~keep_min & _lex_lt(d, i, p_d, p_i)))
    return jnp.where(take, p_d, d), jnp.where(take, p_i, i)


def _bitonic_sort(d, i, *, descending: bool = False):
    """Full in-register bitonic sort of (bq, L) pairs, L a power of two.

    The classic network: stage ``size`` builds sorted runs of that length,
    alternating direction per ``size``-block so adjacent runs form bitonic
    sequences for the next stage.  ``descending`` flips every comparator,
    which sorts the whole row the other way.  O(log^2 L) compare-exchange
    steps, each a constant number of vector ops.
    """
    ln = d.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, d.ndim - 1)
    size = 2
    while size <= ln:
        dir_bit = _lane_bit(lane, size) ^ int(descending)
        j = size // 2
        while j >= 1:
            d, i = _compare_exchange(d, i, lane, j, dir_bit)
            j //= 2
        size *= 2
    return d, i


def _bitonic_merge(d, i):
    """Sort a (bq, L) bitonic sequence ascending; L a power of two.

    The input rises then falls under the :func:`_lex_lt` order, or is any
    rotation of such a sequence (the standard bitonic-merge guarantee).
    log2(L) compare-exchange steps.
    """
    ln = d.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, d.ndim - 1)
    j = ln // 2
    while j >= 1:
        d, i = _compare_exchange(d, i, lane, j, 0)
        j //= 2
    return d, i


def _pad_sentinels(d, i, width: int, *, front: bool = False):
    """Widen (bq, L) pairs to ``width`` with (+inf, `_NO_ROW`) sentinels."""
    bq, ln = d.shape
    if ln == width:
        return d, i
    pd = jnp.full((bq, width - ln), jnp.inf, d.dtype)
    pi = jnp.full((bq, width - ln), _NO_ROW, i.dtype)
    if front:
        return (jnp.concatenate([pd, d], axis=1),
                jnp.concatenate([pi, i], axis=1))
    return (jnp.concatenate([d, pd], axis=1),
            jnp.concatenate([i, pi], axis=1))


def _bitonic_width(k: int, bn: int) -> int:
    """Running top-k width the bitonic merge keeps: a power of two >= k, bn.

    Inside the kernel ``bn`` is 128, so every array the network touches is
    a whole number of 128-lane vregs wide.
    """
    return _next_pow2(max(k, bn))


def _bitonic_topk_merge(best_d, best_i, cand_d, cand_i, k: int):
    """Fold (bq, bn) candidates into the sorted (bq, kb) running top-k.

    The ``"bitonic"`` merge network — same contract as :func:`_topk_merge`
    (ascending (distance, row index), +inf/`_NO_ROW` sentinel slots rank
    last, bitwise ``lax.top_k`` order) in O(log^2 bn + log kb)
    compare-exchange stages instead of k sequential argmin rounds:

    1. bitonic-sort the candidate block *descending* (candidates arrive in
       row order, not distance order), then front-pad it with sentinels to
       the running list's width w — still descending;
    2. the half-cleaner: the lane-wise minimum of the ascending running
       list and the descending candidates holds the w smallest pairs of
       both, as one bitonic sequence (it falls, then rises);
    3. one bitonic merge sorts it; keep the first k columns.

    The running top-k is sorted by construction (the kernel initialises it
    to all-sentinel and this function returns sorted output), so the
    invariant holds inductively across N blocks.  ``best_d`` may have any
    width >= k and ``cand`` any width >= 1 — non-powers-of-two are padded
    with (+inf, `_NO_ROW`) here, which sort strictly after every genuine
    candidate (including +inf-masked real rows, whose indices are
    < `_NO_ROW`).  Inside the kernel both widths are already powers of two
    (:func:`_bitonic_width`), so no padding is traced there.
    """
    w = _next_pow2(max(best_d.shape[1], cand_d.shape[1]))
    best_d, best_i = _pad_sentinels(best_d, best_i, w)
    cand_d, cand_i = _pad_sentinels(cand_d, cand_i,
                                    _next_pow2(cand_d.shape[1]))
    cand_d, cand_i = _bitonic_sort(cand_d, cand_i, descending=True)
    cand_d, cand_i = _pad_sentinels(cand_d, cand_i, w, front=True)
    take = _lex_lt(cand_d, cand_i, best_d, best_i)
    out_d, out_i = _bitonic_merge(jnp.where(take, cand_d, best_d),
                                  jnp.where(take, cand_i, best_i))
    return out_d[:, :k], out_i[:, :k]


#: name -> merge-network implementation (see :data:`MERGE_ALGS`).
_MERGE_FNS = {"bitonic": _bitonic_topk_merge, "argmin": _topk_merge}


def _cam_search_topk_kernel(vr_ref, *refs, levels: int, d_total: int,
                            block_n: int, nj: int, nk: int, masked: bool,
                            counted: bool, merge_alg: str):
    it = iter(refs)
    q_ref, t_ref = next(it), next(it)
    c_ref = next(it) if masked else None
    thr_ref = next(it) if counted else None
    out_i_ref, out_d_ref = next(it), next(it)
    out_c_ref = next(it) if counted else None
    acc_ref, best_d_ref, best_i_ref = next(it), next(it), next(it)
    cnt_ref = next(it) if counted else None

    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when((j == 0) & (kk == 0))
    def _init_best():
        best_d_ref[...] = jnp.full_like(best_d_ref, jnp.inf)
        best_i_ref[...] = jnp.full_like(best_i_ref, jnp.int32(_NO_ROW))
        if counted:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(kk == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]  # (bq, bd) int8 symbols
    t = t_ref[...]  # (bn, bd) int8 symbols
    c = None if c_ref is None else c_ref[...]  # (bn, bd) int8 care flags
    acc_ref[...] = _accumulate(q, t, c, acc_ref[...], levels)

    # D accumulation for block j is complete: fold its bn candidates into the
    # running top-k.  The (bq, bn) distance block dies here, in VMEM.
    @pl.when(kk == nk - 1)
    def _merge():
        row = (j * block_n
               + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1))
        acc = acc_ref[...]
        d = acc if masked else jnp.float32(d_total) - acc
        cand_d = jnp.where(row < vr_ref[0], d, jnp.inf)   # dead/pad rows
        cand_i = jnp.broadcast_to(row, d.shape)
        best_d, best_i = _MERGE_FNS[merge_alg](
            best_d_ref[...], best_i_ref[...], cand_d, cand_i,
            best_d_ref.shape[1])
        best_d_ref[...] = best_d
        best_i_ref[...] = best_i
        if counted:
            # Rows past valid_rows sit at +inf and a threshold is finite, so
            # dead/pad rows can never inflate the count.
            within = (cand_d <= thr_ref[...]).astype(jnp.int32)
            cnt_ref[...] = cnt_ref[...] + jnp.sum(within, axis=1,
                                                  keepdims=True)

    @pl.when((j == nj - 1) & (kk == nk - 1))
    def _finalize():
        out_i_ref[...] = best_i_ref[...]
        out_d_ref[...] = best_d_ref[...]
        if counted:
            out_c_ref[...] = cnt_ref[...]


@functools.partial(jax.jit, static_argnames=("levels", "k", "block_q",
                                             "block_n", "block_d",
                                             "interpret", "merge_alg"))
def cam_search_topk(queries: jnp.ndarray, table: jnp.ndarray,
                    valid_rows: jnp.ndarray, *, levels: int, k: int,
                    care: jnp.ndarray | None = None,
                    count_le: jnp.ndarray | None = None,
                    block_q: int = 128, block_n: int = 128,
                    block_d: int = 512, interpret: bool = False,
                    merge_alg: str = "bitonic"):
    """Streaming top-k search: ((Q, k) int32 rows, (Q, k) f32 distances).

    Same inputs and tiling rules as :func:`cam_search`, plus a traced
    ``valid_rows`` int32 scalar (shape (1,), prefetched to SMEM): rows at
    index >= ``valid_rows`` are masked to +inf *in-kernel*, so fixed-capacity
    slabs need no host-side masking.  Rows come back best-first, ascending
    (distance, row index) — bitwise ``lax.top_k`` over the dense masked
    matrix.  ``k`` must be <= N; HBM output is O(Q*k).

    ``care`` is an optional (N, D) int8 don't-care plane (see
    :func:`cam_search`).  ``count_le`` is an optional (Q, 1) f32 per-query
    threshold: when given, a third (Q, 1) int32 output counts the live rows
    at distance <= threshold — accumulated block-by-block in VMEM alongside
    the running top-k, so multi-match ``match_count`` costs no extra pass
    over the table.  Returns a 2-tuple without ``count_le``, a 3-tuple with.

    ``merge_alg`` picks the per-block merge network (:data:`MERGE_ALGS`):
    ``"bitonic"`` (default, O(log^2 bn + log k) compare-exchange stages) or
    ``"argmin"`` (the original k-round selection, kept as oracle/baseline).
    Both are bitwise-identical by construction; only the op count differs.
    The bitonic network keeps :func:`_bitonic_width` >= k candidates per
    query in VMEM and writes them all; the first k columns are the answer.
    """
    qn, d = queries.shape
    tn, d2 = table.shape
    assert d == d2, (d, d2)
    assert qn % block_q == 0 and tn % block_n == 0 and d % block_d == 0, (
        (qn, tn, d), (block_q, block_n, block_d))
    assert 1 <= k <= tn, (k, tn)
    assert merge_alg in MERGE_ALGS, (merge_alg, MERGE_ALGS)
    assert block_n & (block_n - 1) == 0, (
        f"block_n must be a power of two for the merge network, "
        f"got {block_n}")
    masked = care is not None
    counted = count_le is not None
    if masked:
        assert care.shape == table.shape, (care.shape, table.shape)
    if counted:
        assert count_le.shape == (qn, 1), (count_le.shape, qn)
    nj, nk = tn // block_n, d // block_d

    kw = _bitonic_width(k, block_n) if merge_alg == "bitonic" else k
    kernel = functools.partial(_cam_search_topk_kernel, levels=levels,
                               d_total=d, block_n=block_n, nj=nj, nk=nk,
                               masked=masked, counted=counted,
                               merge_alg=merge_alg)
    in_specs = [
        pl.BlockSpec((block_q, block_d), lambda i, j, kk, vr: (i, kk)),
        pl.BlockSpec((block_n, block_d), lambda i, j, kk, vr: (j, kk)),
    ]
    operands = [queries, table]
    if masked:
        in_specs.append(pl.BlockSpec((block_n, block_d),
                                     lambda i, j, kk, vr: (j, kk)))
        operands.append(care)
    if counted:
        in_specs.append(pl.BlockSpec((block_q, 1),
                                     lambda i, j, kk, vr: (i, 0)))
        operands.append(count_le)
    out_specs = [
        pl.BlockSpec((block_q, kw), lambda i, j, kk, vr: (i, 0)),
        pl.BlockSpec((block_q, kw), lambda i, j, kk, vr: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((qn, kw), jnp.int32),
        jax.ShapeDtypeStruct((qn, kw), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, block_n), jnp.float32),
        pltpu.VMEM((block_q, kw), jnp.float32),
        pltpu.VMEM((block_q, kw), jnp.int32),
    ]
    if counted:
        out_specs.append(pl.BlockSpec((block_q, 1),
                                      lambda i, j, kk, vr: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((qn, 1), jnp.int32))
        scratch_shapes.append(pltpu.VMEM((block_q, 1), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qn // block_q, nj, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(valid_rows, jnp.int32).reshape(1), *operands)
    return (out[0][:, :k], out[1][:, :k], *out[2:])
