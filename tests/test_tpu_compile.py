"""The served CAM-search kernels compile for a TPU v5e.

Interpret mode runs a kernel's semantics on the CPU but not Mosaic, the TPU
kernel compiler, which refuses operations the interpreter accepts (int8
compares, reversals, lane-splitting reshapes).  These tests compile each
kernel variant of the serving path ahead of time for one chip of a
described ``v5e:2x2`` topology — no chip is needed — at the width of the
flat-search deployment (N = 65,536 rows, D = 128 cells), and check that the
program holds the kernel (``tpu_custom_call``) rather than an interpreted
loop.

The topology is described inside module-scoped fixtures, never at import:
only one process may load the TPU compiler library at a time, and every
test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cam_search import ops

N, D, BITS = 65_536, 128, 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
def test_dense_tier_compiles(one_chip, masked):
    q = _spec(one_chip, (64, D), jnp.int8)
    t = _spec(one_chip, (N, D), jnp.int8)
    args = (q, t, _spec(one_chip, (N, D), jnp.int8)) if masked else (q, t)

    def fn(queries, table, care=None):
        return ops.mismatch_counts(queries, table, BITS, False, care=care)

    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("qn,k,masked,counted", [
    (8, 10, False, False),
    (128, 100, False, False),
    (8, 10, True, True),
    (64, 256, False, False),
], ids=["q8-k10", "q128-k100", "q8-k10-masked-counted", "q64-k256"])
def test_fused_bitonic_compiles(one_chip, qn, k, masked, counted):
    args = [_spec(one_chip, (qn, D), jnp.int8),
            _spec(one_chip, (N, D), jnp.int8),
            _spec(one_chip, (), jnp.int32)]
    if masked:
        args.append(_spec(one_chip, (N, D), jnp.int8))
    if counted:
        args.append(_spec(one_chip, (qn,), jnp.float32))

    def fn(queries, table, valid_rows, *extra):
        care = extra[0] if masked else None
        count_le = extra[-1] if counted else None
        return ops.topk_fused(queries, table, k, BITS, valid_rows, False,
                              care=care, count_le=count_le,
                              merge_alg="bitonic")

    assert "tpu_custom_call" in _compiled_text(fn, *args)
