"""Pallas TPU kernels for the SEE-MCAM compute hot-spots.

Each kernel package ships three modules: ``kernel`` (pl.pallas_call +
BlockSpec VMEM tiling), ``ops`` (jitted public wrapper with padding/backend
selection) and ``ref`` (pure-jnp oracle used by the allclose test sweeps).

  cam_search  — multi-bit CAM associative search as one-hot Gram matmuls (MXU)
  hdc_encode  — fused HDC random-projection encode + Z-score quantize
  mibo_mc     — Monte-Carlo MIBO sense-margin device simulation (VPU)
"""

import jax


def interpret_mode(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` flag: an explicit value wins.

    ``None`` compiles the kernel on the TPU and interprets it on the CPU
    (tests, debugging).  Any other backend raises rather than silently
    interpreting a kernel on a device it was not written for.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels run on the TPU (or interpreted on the CPU); "
            f"the default JAX backend is {backend!r}")
    return backend == "cpu"
