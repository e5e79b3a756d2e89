"""``repro.dist`` — the distribution layer: sharding rules + pipeline schedules.

Design: a thin *rule engine* rather than a framework.  The package has two
parts, each usable alone:

* :mod:`repro.dist.specs` — the sharding-rule engine.  ``make_rules(mesh,
  layout)`` returns an immutable :class:`~repro.dist.specs.Rules` whose
  factory methods (``act_resid``, ``act_heads``, ``w2``, ``embed``, ...) map
  *logical tensor roles* to :class:`~jax.sharding.PartitionSpec`s.  Model code
  names roles, never mesh axes; swapping Megatron-TP (``"tp"``) for context
  parallelism (``"cp"``) is a one-string change in the arch config.
  ``constrain(x, spec)`` applies GSPMD constraints and degrades to identity
  where constraints cannot apply (no mesh, manual shard_map regions, foreign
  axes) — so every code path is also a valid single-device program.

* :mod:`repro.dist.pipeline` — GPipe pipeline parallelism over the ``pod``
  mesh axis: ``make_pp_forward`` builds a shard_map whose body runs the
  static microbatch-rotation schedule, ``bubble_fraction`` gives its idle
  cost.  Composes with the rule engine: inner-axis sharding stays GSPMD-auto
  while stages rotate activations manually.
"""

from repro.dist.pipeline import bubble_fraction, make_pp_forward  # noqa: F401
from repro.dist.specs import Rules, constrain, make_rules  # noqa: F401
