"""Jitted wrapper: model-layer flash attention over (B, S, H, dh) tensors."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention import kernel as _k


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention_bshd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         causal: bool = True,
                         interpret: bool | None = None) -> jnp.ndarray:
    """q: (B,S,H,dh); k/v: (B,T,HK,dh) -> (B,S,H,dh) (GQA: H % HK == 0)."""
    interpret = interpret_mode(interpret)
    b, s, h, dh = q.shape
    _, t, hk, _ = k.shape
    group = h // hk
    # (B,S,H,dh) -> (B*H, S, dh) with heads grouped under their KV head
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hk, t, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hk, t, dh)
    blk_q = min(128, s)
    blk_k = min(128, t)
    out = _k.flash_attention(qf, kf, vf, group=group, causal=causal,
                             blk_q=blk_q, blk_k=blk_k, interpret=interpret)
    return out.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
