"""The in-register bitonic top-k merge == the k-round argmin merge, bitwise.

The bitonic network (``kernel._bitonic_topk_merge``) replaced the sequential
argmin selection (``kernel._topk_merge``) as the fused kernel's per-block
fold — O(log^2 bn + log k) compare-exchange stages instead of
O(k * (k + bn)) vector ops — which is what lifted ``am.FUSED_K_MAX`` from 64
to 256.  The two networks must agree **bitwise** on every input the kernel
can feed them:

* the unit itself, vs the argmin merge as oracle AND vs a plain numpy
  lexsort, over random/tie-heavy/degenerate states — including all-+inf
  unfilled running slots (cold-start blocks), sentinel-index tails,
  non-power-of-two k and bn, and bn < k / bn > k both ways;
* end-to-end through ``ops.topk_fused`` vs the dense ``lax.top_k`` path in
  the k in {65..256} band that the argmin ceiling made unreachable;
* the masked (``care=``) and counted (``count_le=``) variants at k > 64;
* k >= N clamping and ``valid_rows`` masking at large k.

Inputs respect the kernel's state invariant: the running (bq, k) best list
is lexicographically sorted by (distance, row index) with **distinct** real
row indices (rows arrive from disjoint table blocks; only the +inf/_NO_ROW
sentinel pair may repeat).  The argmin oracle dedups equal (d, i) pairs, so
feeding it duplicate real rows — impossible in the kernel — would diverge.

The network's lane rotations (``pltpu.roll``) have no eager rule, so the
unit runs under ``jax.jit`` here, outside any kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import am
from repro.kernels.cam_search import kernel as cam_k
from repro.kernels.cam_search import ops as cam_ops
from repro.kernels.cam_search import ref as cam_ref

_NO_ROW = np.iinfo(np.int32).max

_bitonic = jax.jit(cam_k._bitonic_topk_merge, static_argnums=4)


def _running_best(rng, bq, k, *, inf_frac=0.3, sentinel_frac=0.5):
    """A valid running top-k state: sorted, distinct indices, sentinel tail."""
    dist = rng.choice(np.array([0.0, 1.0, 2.0, np.inf], np.float32),
                      (bq, k), p=[(1 - inf_frac) / 3] * 3 + [inf_frac])
    idx = np.stack([rng.choice(1000, k, replace=False)
                    for _ in range(bq)]).astype(np.int32)
    # some +inf slots are unfilled sentinels rather than masked real rows
    sent = np.isinf(dist) & (rng.random((bq, k)) < sentinel_frac)
    idx = np.where(sent, _NO_ROW, idx).astype(np.int32)
    order = np.lexsort((idx, dist), axis=-1)
    return (np.take_along_axis(dist, order, -1),
            np.take_along_axis(idx, order, -1))


def _candidates(rng, bq, bn, *, base=2000, inf_frac=0.25):
    """One (bq, bn) candidate block: distinct indices, some masked to +inf."""
    dist = rng.choice(np.array([0.0, 1.0, 2.0, 3.0, np.inf], np.float32),
                      (bq, bn), p=[(1 - inf_frac) / 4] * 4 + [inf_frac])
    idx = np.broadcast_to(base + np.arange(bn, dtype=np.int32),
                          (bq, bn)).copy()
    return dist, idx


def _numpy_merge(best_d, best_i, cand_d, cand_i, k):
    """Independent oracle: lexsort the concatenation, keep the first k."""
    d = np.concatenate([best_d, cand_d], axis=1)
    i = np.concatenate([best_i, cand_i], axis=1)
    order = np.lexsort((i, d), axis=-1)
    return (np.take_along_axis(d, order, -1)[:, :k],
            np.take_along_axis(i, order, -1)[:, :k])


def _assert_same(got, want, msg=""):
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]),
                                  err_msg=f"{msg} distances")
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]),
                                  err_msg=f"{msg} indices")


# ---------------------------------------------------------------------------
# the merge network as a unit
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(bq=st.integers(1, 6), k=st.integers(1, 24), bn=st.integers(1, 40),
       seed=st.integers(0, 2**31 - 1))
def test_bitonic_matches_argmin_and_numpy(bq, k, bn, seed):
    """Random states, non-power-of-two k and bn on purpose."""
    rng = np.random.default_rng(seed)
    best_d, best_i = _running_best(rng, bq, k)
    cand_d, cand_i = _candidates(rng, bq, bn)
    args = (jnp.asarray(best_d), jnp.asarray(best_i),
            jnp.asarray(cand_d), jnp.asarray(cand_i))
    got = _bitonic(*args, k)
    _assert_same(got, cam_k._topk_merge(*args, k), "vs argmin")
    _assert_same(got, _numpy_merge(best_d, best_i, cand_d, cand_i, k),
                 "vs numpy")


@settings(max_examples=20, deadline=None)
@given(bq=st.integers(1, 4), k=st.integers(1, 16), bn=st.integers(1, 24),
       seed=st.integers(0, 2**31 - 1))
def test_bitonic_tie_heavy_binary(bq, k, bn, seed):
    """Two distance values only: nearly every decision is an index tie."""
    rng = np.random.default_rng(seed)
    best_d = rng.integers(0, 2, (bq, k)).astype(np.float32)
    best_i = np.stack([rng.choice(1000, k, replace=False)
                       for _ in range(bq)]).astype(np.int32)
    order = np.lexsort((best_i, best_d), axis=-1)
    best_d = np.take_along_axis(best_d, order, -1)
    best_i = np.take_along_axis(best_i, order, -1)
    cand_d = rng.integers(0, 2, (bq, bn)).astype(np.float32)
    cand_i = np.broadcast_to(2000 + np.arange(bn, dtype=np.int32),
                             (bq, bn)).copy()
    args = (jnp.asarray(best_d), jnp.asarray(best_i),
            jnp.asarray(cand_d), jnp.asarray(cand_i))
    got = _bitonic(*args, k)
    _assert_same(got, cam_k._topk_merge(*args, k), "vs argmin")
    _assert_same(got, _numpy_merge(best_d, best_i, cand_d, cand_i, k),
                 "vs numpy")


def test_bitonic_all_inf_unfilled_state():
    """Cold start: every running slot is the (+inf, _NO_ROW) sentinel."""
    bq, k, bn = 3, 7, 11
    rng = np.random.default_rng(0)
    best_d = np.full((bq, k), np.inf, np.float32)
    best_i = np.full((bq, k), _NO_ROW, np.int32)
    cand_d, cand_i = _candidates(rng, bq, bn)
    args = (jnp.asarray(best_d), jnp.asarray(best_i),
            jnp.asarray(cand_d), jnp.asarray(cand_i))
    got = _bitonic(*args, k)
    _assert_same(got, cam_k._topk_merge(*args, k))
    # and an all-+inf candidate block leaves the state unchanged
    cand_d = np.full((bq, bn), np.inf, np.float32)
    best_d, best_i = _running_best(rng, bq, k)
    got = _bitonic(
        jnp.asarray(best_d), jnp.asarray(best_i), jnp.asarray(cand_d),
        jnp.full((bq, bn), _NO_ROW, jnp.int32), k)
    _assert_same(got, (best_d, best_i))


@pytest.mark.parametrize("k,bn", [(1, 1), (1, 13), (24, 1), (5, 5),
                                  (33, 17), (64, 128), (100, 128)])
def test_bitonic_degenerate_shapes(k, bn):
    """Edge widths: k=1, bn=1, bn >> k, k >> bn, non-powers-of-two."""
    rng = np.random.default_rng(k * 1000 + bn)
    best_d, best_i = _running_best(rng, 2, k)
    cand_d, cand_i = _candidates(rng, 2, bn)
    args = (jnp.asarray(best_d), jnp.asarray(best_i),
            jnp.asarray(cand_d), jnp.asarray(cand_i))
    got = _bitonic(*args, k)
    _assert_same(got, cam_k._topk_merge(*args, k))


@pytest.mark.parametrize("k,bn", [(10, 128), (100, 128), (256, 128)])
def test_bitonic_is_rotations_and_selects(k, bn):
    """The network Mosaic lowers: no sort/top_k, no reversal, no reshape.

    Each compare-exchange step is two lane rotations per key (partner at
    ``x + j`` and ``x - j``), so the rotation count is the stage count of
    the network: a full sort of the bn candidates, then one merge of the
    running list's power-of-two width.
    """
    rng = np.random.default_rng(1)
    best_d, best_i = _running_best(rng, 8, k)
    cand_d, cand_i = _candidates(rng, 8, bn)
    w = cam_k._bitonic_width(k, bn)
    jaxpr = jax.make_jaxpr(
        lambda a, b, c, d: cam_k._bitonic_topk_merge(a, b, c, d, k))(
            jnp.asarray(best_d), jnp.asarray(best_i),
            jnp.asarray(cand_d), jnp.asarray(cand_i))
    prims = [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]
    assert not {"sort", "top_k", "rev", "reshape", "gather"} & set(prims), (
        sorted(set(prims)))
    log_bn, log_w = bn.bit_length() - 1, w.bit_length() - 1
    stages = log_bn * (log_bn + 1) // 2 + log_w
    assert prims.count("roll") == 4 * stages


# ---------------------------------------------------------------------------
# the previously-unreachable k in {65..256} band, end to end
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(k=st.integers(65, 256), tn=st.integers(1, 300),
       levels=st.sampled_from((2, 8)), seed=st.integers(0, 2**31 - 1))
def test_fused_large_k_band_matches_dense(k, tn, levels, seed):
    """ops.topk_fused == lax.top_k over the dense matrix for k in 65..256,
    including k >= N clamping when the draw makes tn < k."""
    bits = levels.bit_length() - 1
    kq, kt = jax.random.split(jax.random.PRNGKey(seed))
    queries = jax.random.randint(kq, (3, 24), 0, levels)
    table = jax.random.randint(kt, (tn, 24), 0, levels)
    got = cam_ops.topk_fused(queries, table, k=k, bits=bits)
    want = cam_ref.topk(queries, table, k=min(k, tn))
    _assert_same((got[1], got[0]), (want[1], want[0]))


def test_fused_k_max_is_at_least_256_and_dispatches_fused():
    assert am.FUSED_K_MAX >= 256
    codes = jax.random.randint(jax.random.PRNGKey(0), (300, 16), 0, 8)
    t = am.make_table(codes, bits=3)
    queries = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 8)
    am.reset_fused_fallbacks()
    got = am.search(t, queries, k=256, backend="pallas")
    assert am.fused_fallbacks() == 0          # stayed on the fused tier
    want = am.search(t, queries, k=256, backend="ref")
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices))
    np.testing.assert_array_equal(np.asarray(got.distances),
                                  np.asarray(want.distances))


@settings(max_examples=6, deadline=None)
@given(k=st.integers(65, 200), vr=st.integers(0, 260),
       seed=st.integers(0, 2**31 - 1))
def test_fused_large_k_masked_counted_valid_rows(k, vr, seed):
    """The masked (care=) + counted (count_le=) variant at k > 64: indices,
    distances AND the in-kernel multi-match count vs the dense oracle."""
    kq, kt, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    queries = jax.random.randint(kq, (4, 20), 0, 8)
    table = jax.random.randint(kt, (230, 20), 0, 8)
    care = jax.random.randint(kc, (230, 20), 0, 2)
    got = cam_ops.topk_fused(queries, table, k=k, bits=3,
                             valid_rows=jnp.int32(vr), care=care,
                             count_le=jnp.full((4,), 6.0))
    d = cam_ref.mismatch_counts(queries, table, care).astype(jnp.float32)
    d = jnp.where(jnp.arange(230)[None] < vr, d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, min(k, 230))
    _assert_same((got[1], got[0]), (-neg, idx))
    np.testing.assert_array_equal(np.asarray(got[2]),
                                  np.asarray(jnp.sum(d <= 6.0, axis=1)))


# ---------------------------------------------------------------------------
# both merge networks stay selectable and bitwise-identical
# ---------------------------------------------------------------------------

def test_merge_alg_registry():
    assert cam_k.MERGE_ALGS == ("bitonic", "argmin")
    assert set(cam_k._MERGE_FNS) == set(cam_k.MERGE_ALGS)
    queries = jax.random.randint(jax.random.PRNGKey(2), (3, 16), 0, 8)
    table = jax.random.randint(jax.random.PRNGKey(3), (40, 16), 0, 8)
    with pytest.raises(AssertionError):
        cam_ops.topk_fused(queries, table, k=2, bits=3,
                           merge_alg="quickselect")


@settings(max_examples=10, deadline=None)
@given(tn=st.integers(1, 60), k=st.integers(1, 32),
       seed=st.integers(0, 2**31 - 1))
def test_argmin_alg_still_bitwise_identical(tn, k, seed):
    """merge_alg="argmin" (the benchmark baseline) == "bitonic" == dense."""
    kq, kt = jax.random.split(jax.random.PRNGKey(seed))
    queries = jax.random.randint(kq, (3, 12), 0, 4)
    table = jax.random.randint(kt, (tn, 12), 0, 4)
    bit = cam_ops.topk_fused(queries, table, k=k, bits=2,
                             merge_alg="bitonic")
    arg = cam_ops.topk_fused(queries, table, k=k, bits=2,
                             merge_alg="argmin")
    _assert_same(bit, arg)
    _assert_same(bit, cam_ref.topk(queries, table, k=min(k, tn)))
