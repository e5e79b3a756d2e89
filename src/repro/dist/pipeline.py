"""GPipe pipeline parallelism over the ``pod`` mesh axis.

Each pod holds one pipeline *stage* — a contiguous slice of layers, sharded
onto the pod via a leading-layer-axis ``P("pod")`` spec.  The forward is a
``shard_map`` whose body runs the classic GPipe schedule: ``n_micro``
microbatches flow through ``n_stages`` stages over ``n_micro + n_stages - 1``
ticks, activations rotating stage-to-stage through ``ppermute`` after every
tick.  At tick ``t`` stage ``s`` works on microbatch ``t - s``; out-of-range
ticks (the fill/drain bubble) compute garbage that is never read.

The schedule is encoded as a Python loop (the tick/stage structure is static),
so XLA sees a straight-line program with one collective-permute per tick —
exactly the GPipe dataflow, with the bubble cost given by
:func:`bubble_fraction` = (S-1)/(S-1+M).

Outputs: every stage writes its per-tick result into a local ``(n_micro, ...)``
buffer and the shard_map stacks the per-pod buffers along axis 0 (out_specs
``P("pod", ...)``), so callers slice the last pod's block for the valid,
fully-propagated microbatch outputs — see ``tests/test_pipeline.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PP_AXIS = "pod"


def bubble_fraction(stages: int, micro: int) -> float:
    """Idle fraction of the GPipe schedule: (S-1) / (S-1 + M)."""
    if stages < 1 or micro < 1:
        raise ValueError((stages, micro))
    return (stages - 1) / (stages - 1 + micro)


def make_pp_forward(block_apply, n_layers: int, n_stages: int, n_micro: int,
                    mesh: jax.sharding.Mesh, in_spec: P):
    """Build the pipelined forward ``fwd(params, x) -> stacked outputs``.

    Args:
      block_apply: ``(layer_params, x) -> x`` for ONE layer; ``layer_params``
        is the params pytree with the leading layer axis indexed away.
      n_layers: total layer count; must divide evenly into ``n_stages``.
      n_stages: pipeline depth; must equal ``mesh.shape["pod"]``.
      n_micro: number of microbatches (the leading axis of ``x``).
      mesh: device mesh containing a ``pod`` axis.
      in_spec: PartitionSpec of ``x`` — ``(n_micro, batch, ...)`` with the
        microbatch axis unsharded; batch axes may name data axes.

    Returns:
      ``fwd(params, x)`` where ``params`` leaves carry a leading ``n_layers``
      axis (sharded ``P("pod")``) and ``x`` is ``(n_micro, batch, ...)``.
      The result is ``(n_stages * n_micro, batch, ...)``: per-pod output
      buffers stacked along axis 0, the last pod's block holding the valid
      outputs.
    """
    if PP_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {PP_AXIS!r} axis")
    if mesh.shape[PP_AXIS] != n_stages:
        raise ValueError(f"n_stages={n_stages} != mesh {PP_AXIS} size "
                         f"{mesh.shape[PP_AXIS]}")
    if n_layers % n_stages:
        raise ValueError(f"n_layers={n_layers} not divisible by {n_stages}")
    if len(in_spec) and in_spec[0] is not None:
        raise ValueError("microbatch axis of in_spec must be unsharded")
    layers_per_stage = n_layers // n_stages
    n_ticks = n_micro + n_stages - 1
    perm = [(s, (s + 1) % n_stages) for s in range(n_stages)]

    def stage_body(stage_params, x_local):
        """Run one pod's GPipe schedule.

        ``stage_params`` leaves are (layers_per_stage, ...); ``x_local`` is
        (n_micro, batch_local, ...).
        """
        stage = jax.lax.axis_index(PP_AXIS)
        outputs = jnp.zeros_like(x_local)
        carry = jnp.zeros_like(x_local[0])
        for tick in range(n_ticks):
            # Stage 0 feeds itself from the microbatch stream; later stages
            # consume the activation rotated in from the previous stage.
            feed = x_local[tick] if tick < n_micro else carry
            y = jnp.where(stage == 0, feed, carry)
            for layer in range(layers_per_stage):
                y = block_apply(
                    jax.tree.map(lambda leaf: leaf[layer], stage_params), y)
            out_idx = tick - (n_stages - 1)   # microbatch the LAST stage did
            if 0 <= out_idx < n_micro:
                outputs = outputs.at[out_idx].set(y)
            if tick != n_ticks - 1:
                carry = jax.lax.ppermute(y, PP_AXIS, perm)
        return outputs

    out_spec = P(PP_AXIS, *tuple(in_spec)[1:])

    def fwd(params, x):
        """Pipelined forward: layer-stacked ``params``, microbatched ``x``."""
        if x.shape[0] != n_micro:
            raise ValueError(f"x leading axis {x.shape[0]} != n_micro="
                             f"{n_micro}")
        param_specs = jax.tree.map(lambda _: P(PP_AXIS), params)
        return jax.shard_map(stage_body, mesh=mesh,
                             in_specs=(param_specs, in_spec),
                             out_specs=out_spec, check_vma=False)(params, x)

    return fwd
