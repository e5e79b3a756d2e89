"""Jitted wrapper: Monte-Carlo sense-margin study of one SEE-MCAM word."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import fefet, mibo
from repro.kernels import interpret_mode
from repro.kernels.mibo_mc import kernel as _k


@functools.partial(jax.jit, static_argnames=("bits", "n_samples", "interpret"))
def monte_carlo_ml_currents(key: jax.Array, stored: jnp.ndarray,
                            query: jnp.ndarray, bits: int = 3,
                            n_samples: int = 1024,
                            interpret: bool | None = None) -> jnp.ndarray:
    """(S,) matchline currents of a word under V_TH variation (sigma=54 mV).

    ``stored``/``query``: (C,) int symbols.  Worst-case margin studies call
    this twice — once with query == stored (match leakage) and once with a
    single-cell mismatch (worst discharge) — and compare the distributions.
    """
    interpret = interpret_mode(interpret)
    c = stored.shape[0]
    vth1, vth2 = mibo.stored_vths(stored, bits)
    g1, g2 = mibo.search_gate_voltages(query, bits)
    k1, k2 = jax.random.split(key)
    n1 = fefet.sample_vth_variation(k1, (n_samples, c))
    n2 = fefet.sample_vth_variation(k2, (n_samples, c))
    block = 256 if n_samples % 256 == 0 else n_samples
    out = _k.mibo_mc(vth1[None, :] + n1, vth2[None, :] + n2,
                     g1[None, :].astype(jnp.float32),
                     g2[None, :].astype(jnp.float32),
                     block_s=block, interpret=interpret)
    return out[:, 0]
