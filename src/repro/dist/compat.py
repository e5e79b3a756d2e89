"""Ambient-state probes used by :func:`repro.dist.specs.constrain`.

``constrain`` must know whether a mesh is current and whether it is being
traced inside a manual (``shard_map``) region; JAX answers both, the second
only through an internal module.  Both probes live here so that a change of
JAX's internals touches one file.
"""

from __future__ import annotations

import jax


def ambient_mesh():
    """The mesh made current by ``jax.set_mesh``, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if (mesh is None or mesh.empty) else mesh


def in_manual_region() -> bool:
    """True while tracing inside a shard_map/pmap body.

    Mesh axes are bound as named axes there, so sharding constraints naming
    them are invalid — ``constrain`` must become the identity.
    """
    try:
        from jax._src import core as jcore
        return bool(jcore.get_axis_env().axis_sizes)
    except Exception:  # pragma: no cover - internal layout changed
        return False
