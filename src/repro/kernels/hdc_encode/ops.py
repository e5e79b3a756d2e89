"""Jitted public wrapper for the fused HDC encode+quantize kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quantize as q
from repro.kernels import interpret_mode
from repro.kernels.hdc_encode import kernel as _k


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def encode_quantize(x: jnp.ndarray, proj: jnp.ndarray, bits: int = 3,
                    interpret: bool | None = None) -> jnp.ndarray:
    """(B, n) features x (n, D) projection -> (B, D) int32 level codes.

    Pads every axis to block multiples; feature-dim padding contributes zero
    to both the matmul and the row norms, so results are exact.
    """
    interpret = interpret_mode(interpret)
    x = jnp.asarray(x, jnp.float32)
    proj = jnp.asarray(proj, jnp.float32)
    bsz, n = x.shape
    d = proj.shape[1]

    bb = 128 if bsz > 64 else 8
    bd = 512 if d >= 512 else 128
    bk = 128

    def pad(a, axis, mult):
        rem = (-a.shape[axis]) % mult
        if rem == 0:
            return a
        w = [(0, 0)] * a.ndim
        w[axis] = (0, rem)
        return jnp.pad(a, w)

    xp = pad(pad(x, 0, bb), 1, bk)
    pp = pad(pad(proj, 0, bk), 1, bd)
    thr = tuple(float(t) for t in q.gaussian_thresholds_np(bits))
    out = _k.hdc_encode(xp, pp, thresholds=thr, block_b=bb, block_d=bd,
                        block_k=bk, interpret=interpret)
    return out[:bsz, :d]
