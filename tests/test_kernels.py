"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracles.

Sweeps shapes/dtypes parametrically and property-tests with hypothesis, as
required for every kernel in src/repro/kernels/.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import quantize as q
from repro.kernels.cam_search import ops as cam_ops
from repro.kernels.cam_search import ref as cam_ref
from repro.kernels.hdc_encode import ops as enc_ops
from repro.kernels.hdc_encode import ref as enc_ref
from repro.kernels.mibo_mc import ops as mc_ops
from repro.kernels.mibo_mc import ref as mc_ref


# ---------------------------------------------------------------------------
# cam_search
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [1, 2, 3])
@pytest.mark.parametrize("qn,tn,d", [
    (1, 1, 8), (3, 5, 17), (8, 8, 128), (16, 64, 96),
    (130, 40, 520), (256, 128, 512), (7, 129, 1000),
])
def test_cam_search_matches_ref(bits, qn, tn, d):
    key = jax.random.PRNGKey(qn * 1000 + tn * 10 + d + bits)
    kq, kt = jax.random.split(key)
    queries = jax.random.randint(kq, (qn, d), 0, 1 << bits)
    table = jax.random.randint(kt, (tn, d), 0, 1 << bits)
    got = cam_ops.mismatch_counts(queries, table, bits)
    want = cam_ref.mismatch_counts(queries, table)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int32, jnp.uint8])
def test_cam_search_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    queries = jax.random.randint(key, (12, 40), 0, 8).astype(dtype)
    table = jax.random.randint(key, (9, 40), 0, 8).astype(dtype)
    got = cam_ops.mismatch_counts(queries, table, 3)
    want = cam_ref.mismatch_counts(queries.astype(jnp.int32),
                                   table.astype(jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cam_search_exact_and_best_row():
    key = jax.random.PRNGKey(1)
    table = jax.random.randint(key, (33, 64), 0, 8)
    queries = table[jnp.array([4, 31, 0])]
    em = cam_ops.exact_match(queries, table, 3)
    assert bool(em[0, 4]) and bool(em[1, 31]) and bool(em[2, 0])
    br = cam_ops.best_row(queries, table, 3)
    np.testing.assert_array_equal(np.asarray(br), [4, 31, 0])


@settings(max_examples=25, deadline=None)
@given(
    qn=st.integers(1, 20), tn=st.integers(1, 20), d=st.integers(1, 100),
    bits=st.integers(1, 3), seed=st.integers(0, 2**31 - 1),
)
def test_cam_search_property(qn, tn, d, bits, seed):
    key = jax.random.PRNGKey(seed)
    kq, kt = jax.random.split(key)
    queries = jax.random.randint(kq, (qn, d), 0, 1 << bits)
    table = jax.random.randint(kt, (tn, d), 0, 1 << bits)
    got = np.asarray(cam_ops.mismatch_counts(queries, table, bits))
    want = np.asarray(cam_ref.mismatch_counts(queries, table))
    np.testing.assert_array_equal(got, want)
    # invariants: counts bounded by word width; searching a stored row -> 0
    assert got.min() >= 0 and got.max() <= d
    got_self = np.asarray(cam_ops.mismatch_counts(table[:1], table, bits))
    assert got_self[0, 0] == 0


# ---------------------------------------------------------------------------
# hdc_encode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [1, 2, 3])
@pytest.mark.parametrize("b,n,d", [
    (1, 4, 16), (5, 30, 100), (8, 128, 512), (130, 617, 1024), (64, 75, 333),
])
def test_hdc_encode_matches_ref(bits, b, n, d):
    key = jax.random.PRNGKey(b + n + d + bits)
    kx, kp = jax.random.split(key)
    x = jax.random.normal(kx, (b, n), jnp.float32)
    proj = jax.random.normal(kp, (n, d), jnp.float32)
    got = enc_ops.encode_quantize(x, proj, bits)
    want = enc_ref.encode_quantize(x, proj, q.gaussian_thresholds(bits))
    # the fused kernel and the oracle differ only by f32 summation order;
    # a handful of values sitting exactly on a threshold may flip one level.
    got, want = np.asarray(got), np.asarray(want)
    mismatch_frac = (got != want).mean()
    assert mismatch_frac < 5e-3, mismatch_frac
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


@settings(max_examples=20, deadline=None)
@given(b=st.integers(1, 16), n=st.integers(2, 64), d=st.integers(1, 128),
       bits=st.integers(1, 3), seed=st.integers(0, 2**31 - 1))
def test_hdc_encode_property(b, n, d, bits, seed):
    key = jax.random.PRNGKey(seed)
    kx, kp = jax.random.split(key)
    x = jax.random.normal(kx, (b, n), jnp.float32)
    proj = jax.random.normal(kp, (n, d), jnp.float32)
    got = np.asarray(enc_ops.encode_quantize(x, proj, bits))
    assert got.shape == (b, d)
    assert got.min() >= 0 and got.max() < (1 << bits)
    # scaling the input row leaves codes invariant (Z-score normalisation)
    got2 = np.asarray(enc_ops.encode_quantize(3.7 * x, proj, bits))
    np.testing.assert_array_equal(got, got2)


def test_hdc_encode_levels_balanced():
    """CDF-equalized quantization => near-uniform level usage."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (64, 256), jnp.float32)
    proj = jax.random.normal(jax.random.PRNGKey(8), (256, 1024), jnp.float32)
    codes = np.asarray(enc_ops.encode_quantize(x, proj, 3)).ravel()
    freq = np.bincount(codes, minlength=8) / codes.size
    np.testing.assert_allclose(freq, 0.125, atol=0.02)


# ---------------------------------------------------------------------------
# mibo_mc
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,c", [(256, 32), (512, 8), (1024, 64), (100, 17)])
def test_mibo_mc_matches_ref(s, c):
    from repro.core import fefet, mibo
    key = jax.random.PRNGKey(s + c)
    ks, kq, k1, k2 = jax.random.split(key, 4)
    stored = jax.random.randint(ks, (c,), 0, 8)
    query = jax.random.randint(kq, (c,), 0, 8)
    vth1, vth2 = mibo.stored_vths(stored, 3)
    g1, g2 = mibo.search_gate_voltages(query, 3)
    n1 = fefet.sample_vth_variation(k1, (s, c))
    n2 = fefet.sample_vth_variation(k2, (s, c))
    from repro.kernels.mibo_mc import kernel as _k
    block = 256 if s % 256 == 0 else s
    got = _k.mibo_mc(vth1[None] + n1, vth2[None] + n2,
                     g1[None].astype(jnp.float32), g2[None].astype(jnp.float32),
                     block_s=block, interpret=True)
    want = mc_ref.ml_currents(vth1[None] + n1, vth2[None] + n2,
                              g1[None], g2[None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-12)


def test_mibo_mc_margin_separation():
    """Match-case leakage and worst-case (1-cell mismatch) discharge current
    distributions must be separated — the Fig. 9 robustness claim."""
    from repro.core import fefet
    key = jax.random.PRNGKey(3)
    stored = jax.random.randint(key, (32,), 0, 8)
    i_match = mc_ops.monte_carlo_ml_currents(key, stored, stored,
                                             n_samples=512)
    worst = stored.at[0].set((stored[0] + 1) % 8)  # adjacent-level mismatch
    i_mm = mc_ops.monte_carlo_ml_currents(key, stored, worst, n_samples=512)
    # worst-case mismatch current must exceed match leakage with clear margin
    # (adjacent-level mismatch at sigma=54 mV: ~2.8 sigma of ladder spacing)
    assert float(jnp.percentile(i_mm, 1.0)) > 3 * float(
        jnp.percentile(i_match, 99.0))
    assert float(jnp.min(i_mm)) > float(jnp.max(i_match))


# ---------------------------------------------------------------------------
# backend selection: compiled on the TPU, interpreted on the CPU, else refused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_interpret_mode_follows_backend(monkeypatch, backend, want):
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert interpret_mode(True) is True and interpret_mode(False) is False
    if want is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            interpret_mode(None)
    else:
        assert interpret_mode(None) is want
