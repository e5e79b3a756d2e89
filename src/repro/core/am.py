"""Functional associative-search API — the SEE-MCAM primitive as pure JAX.

The paper's contribution packaged for system use: an immutable :class:`AMTable`
of multi-bit codes plus one pure entry point :func:`search` that runs batched
top-k / threshold associative lookups over it.  Everything is data-in/data-out:

  >>> table = am.make_table(codes, bits=3, distance="l1")
  >>> table = am.append(table, more_codes)             # returns a NEW table
  >>> res = am.search(table, queries, k=4, threshold=2, backend="pallas")
  >>> res.indices, res.distances, res.exact, res.matched   # all (Q, k)

``AMTable`` and :class:`AMSearchResult` are registered pytrees, so ``search``
jits as a whole (the table is a traced argument — no hidden host state), vmaps
over query batches, and passes through ``shard_map``.  :func:`search_sharded`
row-partitions the table over the ``model`` mesh axis (the paper's multi-bank
organisation) and merges per-bank top-k candidates — with a flat all-gather on
narrow meshes or a hierarchical tree merge on wide ones (see "Merge
topologies" below).

Backends are plugins registered through :func:`register_backend`; ``"ref"``
(pure jnp oracle), ``"pallas"`` (MXU one-hot Gram kernel,
:mod:`repro.kernels.cam_search`), ``"analog"`` (behavioural FeFET circuit
model, :mod:`repro.core.cam_array`) and ``"analog_cal"`` (the same circuit
model with its L1 readout calibrated back to digital level units through the
affine overdrive fit) ship by default.

The full stack contract — layer map, capability tiers, tie-break guarantee,
merge-topology decision table — is documented in ``docs/ARCHITECTURE.md``
(machine-checked against this module by ``tests/test_docs_contract.py``).

Backend capability tiers
------------------------
Every backend provides the **dense** tier: ``fn(queries, codes, bits,
distance) -> (Q, N)`` distances; :func:`search` then extracts top-k with
``lax.top_k``.  A backend may additionally register a **fused** tier —
``fn(queries, codes, bits, distance, k=, valid_rows=) -> ((Q, k) int32
rows, (Q, k) float32 distances)`` — that computes top-k inside its own
kernel without ever materialising the (Q, N) matrix (O(Q*k) memory traffic
instead of O(Q*N)).  :func:`search` and :func:`search_sharded` dispatch to
the fused tier automatically when the backend has one and ``k`` <=
:data:`FUSED_K_MAX`; the two tiers are required to be **bitwise-identical**
(indices, distances, tie-breaks, masked rows), so the dispatch is invisible
to callers.  A fused tier must honour the tie-break ordering guarantee:
ascending (distance, row index), lowest row index winning every tie —
including among +inf masked rows.  ``"pallas"`` ships a fused tier
(:func:`repro.kernels.cam_search.ops.topk_fused`); ``"ref"`` and
``"analog"`` are dense-only.

A third **masked** tier (``"ref"`` and ``"pallas"``) adds ternary
don't-care semantics: tier functions accept ``care=``, an (N, D) 0/1 plane
stored on the table (:func:`make_table` with ``care_mask=``), and positions
with ``care == 0`` never count as mismatches.  An all-ones plane is
bitwise-identical to no plane at all, on every tier.  On top of either tier,
``search(..., matches=M)`` switches the *result* semantics to multi-match
(:class:`AMMultiMatchResult`): all rows within threshold in a fixed-width
window, priority (lowest (distance, index)) entry first, with an exact
``match_count`` and an ``overflow`` flag — the TCAM/TLB answer shape.

Merge topologies (``search_sharded``'s cross-bank candidate reduction)
----------------------------------------------------------------------
Per-bank top-k candidate lists are reduced to the global top-k by one of
three strategies, selected by the ``merge=`` argument:

* ``"allgather"`` — every bank broadcasts its (Q, k_local) candidate pair to
  every other bank, then re-ranks locally.  One collective round; per-device
  traffic O(Q * k * banks).  Right for narrow meshes.
* ``"tree"``      — ceil(log2(banks)) rounds of pairwise ``ppermute`` +
  k-way lexicographic (distance, global-row-index) merge, each round keeping
  only the running top-k.  Per-device traffic O(Q * k * log banks) — flat
  per bank as the array scales out, the paper's scalability claim.
* ``"ring"``      — a reduce-scatter over query chunks (banks-1 ``ppermute``
  rounds, each bank folding its candidates into a rotating Q/banks chunk)
  plus one chunk-sized all-gather.  Per-device traffic O(Q * k),
  independent of bank count — bandwidth-optimal, the right topology when
  k >> banks — at 2*(banks-1) rounds of latency.
* ``"auto"``      — ``"allgather"`` below :data:`TREE_MERGE_MIN_BANKS`
  banks; at or above it, ``"ring"`` when ``k >=``
  :data:`RING_MERGE_MIN_K_PER_BANK` ``* banks``, else ``"tree"``.

All strategies are bitwise-identical to single-device :func:`search` —
the lexicographic merge preserves the (distance, row index) tie-break
exactly — so the choice is purely a traffic/latency trade.

Distance-unit contract (every backend must satisfy it)
------------------------------------------------------
A dense-tier backend is ``fn(queries, codes, bits, distance) -> (Q, N)
array`` where the entries are distances in units of **binary cell
mismatches**:

* ``distance="hamming"`` — the number of differing multi-bit symbols;
* ``distance="l1"``      — the total level distance ``sum_d |q_d - t_d|``
  (each symbol contributes its thermometer-code Hamming distance).

Requirements:

* an entry is ``0`` **iff** the query word equals the stored word exactly
  (digital backends return exact integers; analog backends may return floats
  but must keep every true match below ``EXACT_MATCH_EPS`` = 0.5 and every
  mismatch above it — the analog unit is one LSB-mismatch discharge current,
  :func:`repro.core.mibo.lsb_mismatch_current`);
* for digital backends the value must equal the integer distance exactly, so
  ``threshold`` semantics are bit-precise;
* the analog ``"l1"`` path reports the *physical* ML discharge in LSB units —
  monotone in the level distance of each cell but not numerically equal to
  the digital L1 sum (the device's overdrive response is affine, not
  proportional); rankings agree on exact matches and single-cell gaps.  The
  ``"analog_cal"`` backend closes that gap: it inverts the affine fit
  ``i_ml ~= a * mismatches + b * L1``
  (:func:`repro.core.mibo.overdrive_response_fit`) so its ``"l1"`` values
  are digital-equivalent level distances and half-integer thresholds carry
  over between analog and digital backends unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fefet, mibo

#: Distances below this are exact word matches (half of one LSB mismatch —
#: the smallest distance any backend may report for a true mismatch is ~1.0).
EXACT_MATCH_EPS = 0.5

DISTANCES = ("hamming", "l1")


# ---------------------------------------------------------------------------
# AMTable — the immutable code store
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True)
class AMTable:
    """Immutable multi-bit code table (a registered pytree).

    Children: ``codes`` (N, D) int32 symbols in [0, 2**bits), the optional
    per-row ``meta`` array (e.g. value ids for an associative cache — any
    array whose leading axis aligns with rows), and the optional ``care``
    plane — (N, D) int32 0/1 flags marking which symbol positions of each
    row participate in distance (0 = ternary don't-care cell; positions with
    ``care == 0`` never count as mismatches).  ``bits`` and ``distance``
    are static aux data, so a jitted function specialises on them exactly
    like on shapes.

    Registered *with keys* so key-path flattens name the children
    (``.codes`` / ``.meta`` / ``.care``) instead of positional flat
    indices — checkpoint manifests built from key paths
    (:mod:`repro.checkpoint.checkpointer`) stay self-describing and
    stable across the optional children being present or ``None``.
    """

    codes: jnp.ndarray
    meta: jnp.ndarray | None = None
    care: jnp.ndarray | None = None
    bits: int = 3
    distance: str = "hamming"

    def tree_flatten(self):
        """Flatten into (codes, meta, care) children + (bits, distance) aux."""
        return (self.codes, self.meta, self.care), (self.bits, self.distance)

    def tree_flatten_with_keys(self):
        """Keyed flatten: ``.codes`` / ``.meta`` / ``.care`` named children."""
        ga = jax.tree_util.GetAttrKey
        return ((ga("codes"), self.codes), (ga("meta"), self.meta),
                (ga("care"), self.care)), (self.bits, self.distance)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from the children/aux pair of :meth:`tree_flatten`."""
        codes, meta, care = children
        return cls(codes=codes, meta=meta, care=care, bits=aux[0],
                   distance=aux[1])

    @property
    def n_rows(self) -> int:
        """Stored row (word) count N."""
        return self.codes.shape[0]

    @property
    def width(self) -> int:
        """Word width D in multi-bit symbols."""
        return self.codes.shape[1]


def _check_care(care_mask, codes) -> jnp.ndarray | None:
    """Normalise a care plane to (N, D) int32 0/1 aligned with ``codes``."""
    if care_mask is None:
        return None
    care = jnp.asarray(care_mask)
    if care.shape != codes.shape:
        raise ValueError(
            f"care_mask shape {care.shape} != codes shape {codes.shape}")
    return (care != 0).astype(jnp.int32)


def make_table(codes, *, bits: int = 3, distance: str = "hamming",
               meta=None, care_mask=None) -> AMTable:
    """Build an :class:`AMTable` from (N, D) integer symbol codes.

    Args:
      codes: (N, D) integer symbols in [0, 2**bits).
      bits: bits per stored symbol (static).
      distance: ``"hamming"`` or ``"l1"`` (static; see the unit contract).
      meta: optional per-row array whose leading axis aligns with rows.
      care_mask: optional (N, D) ternary care plane — nonzero marks a cared
        position, 0 a don't-care cell excluded from distance.  Requires a
        backend with the ``"masked"`` capability tier at search time; an
        all-nonzero mask is bitwise-identical to no mask.

    Returns:
      A new immutable :class:`AMTable`.
    """
    if distance not in DISTANCES:
        raise ValueError(f"unknown distance {distance!r}; expected {DISTANCES}")
    codes = jnp.asarray(codes, jnp.int32)
    if codes.ndim != 2:
        raise ValueError(f"codes must be (N, D), got {codes.shape}")
    if meta is not None:
        meta = jnp.asarray(meta)
        if meta.shape[:1] != codes.shape[:1]:
            raise ValueError(
                f"meta leading axis {meta.shape[:1]} != rows {codes.shape[:1]}")
    return AMTable(codes=codes, meta=meta, care=_check_care(care_mask, codes),
                   bits=bits, distance=distance)


def write(table: AMTable, codes, meta=None, care_mask=None) -> AMTable:
    """Replace the stored codes, returning a new table (pure update)."""
    return make_table(codes, bits=table.bits, distance=table.distance,
                      meta=meta, care_mask=care_mask)


def append(table: AMTable, codes, meta=None, care_mask=None) -> AMTable:
    """Append (M, D) rows, returning a new table.

    ``meta`` and ``care_mask`` presence must each match the table's — a
    ternary table stays ternary row-for-row and a plain table stays plain.
    """
    codes = jnp.asarray(codes, jnp.int32)
    if codes.ndim == 1:
        codes = codes[None]
    if codes.shape[-1] != table.width:
        raise ValueError(
            f"appended width {codes.shape[-1]} != table width {table.width}")
    new_codes = jnp.concatenate([table.codes, codes], axis=0)
    if (table.meta is None) != (meta is None):
        raise ValueError("append meta presence must match the table's")
    if (table.care is None) != (care_mask is None):
        raise ValueError("append care_mask presence must match the table's")
    new_meta = None
    if meta is not None:
        meta = jnp.atleast_1d(jnp.asarray(meta))
        if meta.shape[:1] != codes.shape[:1]:
            raise ValueError(
                f"meta leading axis {meta.shape[:1]} != appended rows "
                f"{codes.shape[:1]}")
        new_meta = jnp.concatenate([table.meta, meta], axis=0)
    new_care = None
    if care_mask is not None:
        care = jnp.asarray(care_mask)
        if care.ndim == 1:
            care = care[None]
        new_care = jnp.concatenate([table.care, _check_care(care, codes)],
                                   axis=0)
    return AMTable(codes=new_codes, meta=new_meta, care=new_care,
                   bits=table.bits, distance=table.distance)


def delete(table: AMTable, rows) -> AMTable:
    """Drop rows by index array or boolean eviction mask; returns a new table.

    ``rows`` is either an integer index array or an (N,) boolean mask where
    ``True`` marks rows to remove (the eviction-mask path: policies compute
    a kill mask over ``meta`` timestamps and delete in one call).
    Shape-changing, so not jittable — intended for host-side table
    maintenance (cache eviction, tombstone compaction).
    """
    rows = np.asarray(rows)
    if rows.dtype == np.bool_:
        if rows.shape != (table.n_rows,):
            raise ValueError(
                f"boolean delete mask shape {rows.shape} != rows "
                f"({table.n_rows},)")
        rows = np.flatnonzero(rows)
    else:
        # a negative index would wrap onto the wrong row (and a too-large
        # one only errors deep inside jnp.delete) — reject both by name
        idx = rows.reshape(-1).astype(np.int64)
        bad = idx[(idx < 0) | (idx >= table.n_rows)]
        if bad.size:
            raise ValueError(
                f"delete indices out of range [0, {table.n_rows}): "
                f"{sorted(set(bad.tolist()))}")
    new_codes = jnp.delete(table.codes, rows, axis=0)
    new_meta = None if table.meta is None else jnp.delete(table.meta, rows,
                                                          axis=0)
    new_care = None if table.care is None else jnp.delete(table.care, rows,
                                                          axis=0)
    return AMTable(codes=new_codes, meta=new_meta, care=new_care,
                   bits=table.bits, distance=table.distance)


# ---------------------------------------------------------------------------
# Serving meta: per-row timestamps for eviction policies
# ---------------------------------------------------------------------------
#
# ``repro.serve.am_service`` stores tables whose ``meta`` is an (N, 2) float32
# array of timestamps — column META_INSERT is the insert time, column
# META_LAST_HIT the last exact-hit time.  LRU eviction orders rows by
# META_LAST_HIT, TTL expiry by ``now - META_INSERT``.  The helpers below are
# the only code that knows the column layout.

#: ``meta[:, META_INSERT]`` — when the row was appended.
META_INSERT = 0
#: ``meta[:, META_LAST_HIT]`` — when the row last matched exactly.
META_LAST_HIT = 1


def serving_meta(n: int, now) -> jnp.ndarray:
    """(n, 2) float32 timestamp meta for freshly inserted rows.

    Both columns start at ``now``: a row that has never been hit is exactly
    as recently-used as its insertion time.
    """
    return jnp.full((n, 2), now, jnp.float32)


def touch(table: AMTable, rows, now) -> AMTable:
    """Set the last-hit timestamp of ``rows`` to ``now`` (pure, jittable).

    ``rows`` may be traced; out-of-range indices are dropped, so callers can
    pass ``table.n_rows`` as a "no row" sentinel for queries that missed —
    the scatter then updates exactly the rows that hit, inside the same
    compiled search dispatch (no host round-trip to maintain LRU order).
    """
    if table.meta is None:
        raise ValueError("touch() needs a table with (N, 2) timestamp meta — "
                         "build it with meta=serving_meta(n, now)")
    meta = table.meta.at[rows, META_LAST_HIT].set(
        jnp.asarray(now, jnp.float32), mode="drop")
    return dataclasses.replace(table, meta=meta)


# ---------------------------------------------------------------------------
# Backend registry — two capability tiers (dense / fused)
# ---------------------------------------------------------------------------

BackendFn = Callable[[jnp.ndarray, jnp.ndarray, int, str], jnp.ndarray]
#: fused tier: fn(queries, codes, bits, distance, *, k, valid_rows)
#: -> ((Q, k) int32 row indices, (Q, k) float32 distances), best-first,
#: ties (including +inf masked rows) to the lowest row index.
FusedBackendFn = Callable[..., tuple[jnp.ndarray, jnp.ndarray]]

#: Largest ``k`` routed to a backend's fused tier.  The streaming kernel's
#: per-block fold is a bitonic merge network — O(log^2 bn + log k)
#: compare-exchange stages, not the k sequential argmin rounds that once
#: capped this at 64 — so the ceiling now sits where the (bq, k) running
#: state stops paying for itself in VMEM; beyond it the dense tier +
#: ``lax.top_k`` is the right tool anyway (k ~ N).  Both tiers are
#: bitwise-identical, so the cutover is invisible in results — but not in
#: cost, so crossings are counted (see :func:`fused_fallbacks`).
FUSED_K_MAX = 256

# Count of times a fused-capable backend was forced onto the dense O(Q*N)
# path because k (or the match window) exceeded FUSED_K_MAX.  The dispatch
# is static (k and FUSED_K_MAX are Python ints), so the counter ticks at
# trace time: once per compiled signature under jit, once per call when
# eager.  Either way a nonzero reading means the fused ceiling is being
# crossed somewhere — previously this downgrade was silent and showed up
# only as a slowdown.
_fused_fallback_count = 0


def _note_fused_fallback() -> None:
    global _fused_fallback_count
    _fused_fallback_count += 1


def fused_fallbacks() -> int:
    """How often a fused-capable backend fell back to the dense tier.

    Counts dispatch decisions in :func:`search` / :func:`search_sharded`
    where the backend registers a fused tier but ``k`` (or ``matches``)
    exceeds :data:`FUSED_K_MAX` — the silent O(Q*k) -> O(Q*N) downgrade
    this counter makes observable.  Ticks at trace time (see the note on
    ``_fused_fallback_count``); :class:`repro.serve.am_service.AMService`
    additionally counts per *request group* in ``stats()``.
    """
    return _fused_fallback_count


def reset_fused_fallbacks() -> None:
    """Zero the :func:`fused_fallbacks` counter (test/bench isolation)."""
    global _fused_fallback_count
    _fused_fallback_count = 0


@dataclasses.dataclass(frozen=True)
class _Backend:
    """Registry entry: the mandatory dense tier + optional fused tier.

    ``masked`` marks backends whose tier functions additionally accept the
    ternary ``care=`` keyword (the "masked" capability); ``fused_count``
    marks a fused tier that also accepts ``count_le=`` per-query thresholds
    and then returns a third (Q,) int32 within-threshold count (the
    multi-match fast path).
    """

    dense: BackendFn
    fused: FusedBackendFn | None = None
    masked: bool = False
    fused_count: bool = False

    @property
    def capabilities(self) -> tuple[str, ...]:
        """Tier names this backend implements, dense always first."""
        caps = ["dense"]
        if self.fused is not None:
            caps.append("fused")
        if self.masked:
            caps.append("masked")
        return tuple(caps)


_BACKENDS: dict[str, _Backend] = {}
DEFAULT_BACKEND = "ref"


def register_backend(name: str, fn: BackendFn, *,
                     fused: FusedBackendFn | None = None,
                     masked: bool = False,
                     fused_count: bool = False) -> None:
    """Register (or replace) a search backend under ``name``.

    Args:
      name: registry key callers pass as ``backend=``.
      fn: the dense tier — ``fn(queries, codes, bits, distance)`` returning
        the (Q, N) distance matrix under the module-level unit contract.
      fused: optionally the fused tier — a direct top-k
        ``fn(queries, codes, bits, distance, k=, valid_rows=)`` that must be
        bitwise-identical to dense + ``lax.top_k`` (see module docstring).
      masked: declare the masked (ternary) tier: every tier function accepts
        a ``care=`` keyword ((N, D) 0/1 plane; don't-care positions never
        mismatch) and an all-ones plane is bitwise-identical to ``None``.
      fused_count: the fused tier additionally accepts ``count_le=`` and
        returns ``(rows, distances, counts)`` — required for the fused
        multi-match path (:func:`search` with ``matches=``).
    """
    _BACKENDS[name] = _Backend(dense=fn, fused=fused, masked=masked,
                               fused_count=fused_count)


def get_backend(name: str) -> BackendFn:
    """The dense-tier function registered under ``name``."""
    return _get_entry(name).dense


def _get_entry(name: str) -> _Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {backend_names()}"
        ) from None


def backend_names() -> tuple[str, ...]:
    """Names of every registered backend, registration order."""
    return tuple(_BACKENDS)


def backend_capabilities(name: str) -> tuple[str, ...]:
    """Capability tiers of the backend registered under ``name``.

    Always starts with ``"dense"``; ``"fused"`` when a fused top-k tier is
    registered as well, ``"masked"`` when the backend accepts ternary care
    planes (``docs/ARCHITECTURE.md`` backend table — machine-checked).

    A ``"fused"`` capability only engages for ``k <= FUSED_K_MAX``; beyond
    that ``search``/``search_sharded`` silently run the dense tier
    (bitwise-identical, asymptotically slower).  :func:`fused_fallbacks`
    counts those downgrades, and serving exposes them per request group as
    ``AMService.stats()["fused_fallbacks"]``.
    """
    return _get_entry(name).capabilities


def _resolve_backend(backend: str | BackendFn | None) -> _Backend:
    if backend is None:
        return _BACKENDS[DEFAULT_BACKEND]
    if callable(backend):
        return _Backend(dense=backend)     # raw callables are dense-tier
    return _get_entry(backend)


def thermometer(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(..., D) levels in [0, 2^b) -> (..., D*(2^b-1)) binary thermometer.

    ``|a - b| = Hamming(therm(a), therm(b))`` — the expansion digital
    backends share to realise the L1 distance on Hamming hardware.
    """
    m = 1 << bits
    rungs = jnp.arange(1, m)
    out = (codes[..., None] >= rungs).astype(jnp.int32)
    return out.reshape(*codes.shape[:-1], codes.shape[-1] * (m - 1))


def _expand_l1(queries, codes, bits, distance):
    """Apply the thermometer trick for digital backends in L1 mode."""
    if distance == "l1" and bits > 1:
        return thermometer(queries, bits), thermometer(codes, bits), 1
    return queries, codes, bits


def _expand_care_l1(care, bits, distance):
    """Widen a care plane to match :func:`_expand_l1`'s thermometer codes.

    A don't-care *symbol* excludes all ``2**bits - 1`` of its thermometer
    rungs, so the plane is repeated per rung — masked L1 distance is then
    ``sum_d care_d * |q_d - t_d|`` exactly.
    """
    if care is not None and distance == "l1" and bits > 1:
        return jnp.repeat(care, (1 << bits) - 1, axis=-1)
    return care


@functools.partial(jax.jit, static_argnames=("bits", "distance"))
def _ref_backend(queries, codes, bits, distance, care=None):
    # jitted so eager callers get a fused compare-reduce instead of
    # materialising the (Q, N, D) broadcast comparison
    care = _expand_care_l1(care, bits, distance)
    queries, codes, bits = _expand_l1(queries, codes, bits, distance)
    diff = queries[:, None, :] != codes[None, :, :]
    if care is not None:
        diff = diff & (care[None, :, :] != 0)
    return jnp.sum(diff, axis=-1, dtype=jnp.int32)


def _pallas_backend(queries, codes, bits, distance, care=None):
    from repro.kernels.cam_search import ops as cam_ops
    care = _expand_care_l1(care, bits, distance)
    queries, codes, bits = _expand_l1(queries, codes, bits, distance)
    return cam_ops.mismatch_counts(queries, codes, bits, care=care)


def _pallas_fused_backend(queries, codes, bits, distance, *, k, valid_rows,
                          care=None, count_le=None):
    # The L1 thermometer expansion widens D, never the row axis, so the
    # in-kernel valid_rows mask applies unchanged.
    from repro.kernels.cam_search import ops as cam_ops
    care = _expand_care_l1(care, bits, distance)
    queries, codes, bits = _expand_l1(queries, codes, bits, distance)
    return cam_ops.topk_fused(queries, codes, k=k, bits=bits,
                              valid_rows=valid_rows, care=care,
                              count_le=count_le)


def make_analog_backend(variation_key: jax.Array | None = None,
                        params: fefet.FeFETParams = fefet.DEFAULT,
                        calibrated: bool = False) -> BackendFn:
    """Build an analog (device-model) backend, optionally with V_TH variation.

    ``"hamming"`` counts cells whose MIBO node D charged; ``"l1"`` reports the
    graded matchline discharge current in LSB-mismatch units
    (:func:`repro.core.mibo.lsb_mismatch_current`), the paper's analog
    nearest-match ranking.  The default registered ``"analog"`` backend is
    this with no variation; register a keyed instance for robustness studies::

        am.register_backend("analog_mc", am.make_analog_backend(key))

    With ``calibrated=True`` the ``"l1"`` readout is inverted through the
    affine overdrive-response fit
    (:func:`repro.core.mibo.overdrive_response_fit`): a matchline discharge
    ``i_ml ~= a * mismatches + b * L1`` maps back to the digital-equivalent
    level distance ``(i_ml - a * mismatches) / b``, so analog thresholds
    compare directly with digital ones (the registered ``"analog_cal"``
    backend).  The residual is the fit error of the device's slightly
    super-affine response — well under half a level per mismatching cell —
    so half-integer thresholds are exact.

    Variation-keyed instances are **not shard-safe**: the noise is drawn from
    ``codes.shape``, so under :func:`search_sharded` every bank would draw
    the same realisation for different rows (and none would match the
    single-device draw) — run Monte-Carlo studies through :func:`search`.

    Args:
      variation_key: optional PRNG key for per-cell V_TH variation noise.
      params: FeFET device parameters the circuit model evaluates under.
      calibrated: invert the affine overdrive fit so ``"l1"`` distances come
        back in digital level units instead of raw LSB-current units.

    Returns:
      A dense-tier :data:`BackendFn`.
    """
    def _backend(queries, codes, bits, distance):
        from repro.core import cam_array
        noise1 = noise2 = None
        if variation_key is not None:
            k1, k2 = jax.random.split(variation_key)
            noise1 = fefet.sample_vth_variation(k1, codes.shape, params)
            noise2 = fefet.sample_vth_variation(k2, codes.shape, params)
        mismatch, i_ml = cam_array.analog_search_batch(
            codes, queries, bits, noise1, noise2, params)
        if distance == "hamming":
            return mismatch
        if calibrated:
            a, b = mibo.overdrive_response_fit(bits, params)
            return (i_ml - a * mismatch) / b
        return i_ml / mibo.lsb_mismatch_current(bits, params)

    return _backend


register_backend("ref", _ref_backend, masked=True)
register_backend("pallas", _pallas_backend, fused=_pallas_fused_backend,
                 masked=True, fused_count=True)
register_backend("analog", make_analog_backend())
register_backend("analog_cal", make_analog_backend(calibrated=True))


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class AMSearchResult:
    """Top-k outcome of one batched associative search (a registered pytree).

    All fields are (Q, k) — or (k,) when a single 1-D query was given —
    ordered best-first (ascending distance, ties broken by lowest row index).
    """

    indices: jnp.ndarray     # int32 row indices of the k nearest rows
    distances: jnp.ndarray   # float32 distances (unit: binary cell mismatches)
    exact: jnp.ndarray       # bool — distance below EXACT_MATCH_EPS
    matched: jnp.ndarray     # bool — within `threshold` (== exact if None)

    def tree_flatten(self):
        """Flatten into the four result arrays (no aux data)."""
        return (self.indices, self.distances, self.exact, self.matched), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from the children of :meth:`tree_flatten`."""
        del aux
        return cls(*children)

    @property
    def best_row(self) -> jnp.ndarray:
        """(Q,) index of the single nearest row (the legacy readout)."""
        return self.indices[..., 0]

    @property
    def best_distance(self) -> jnp.ndarray:
        """(Q,) distance of the single nearest row."""
        return self.distances[..., 0]


def _finalize(indices, distances, threshold, squeeze) -> AMSearchResult:
    exact = distances < EXACT_MATCH_EPS
    matched = exact if threshold is None else distances <= threshold
    if squeeze:
        indices, distances = indices[0], distances[0]
        exact, matched = exact[0], matched[0]
    return AMSearchResult(indices=indices, distances=distances, exact=exact,
                          matched=matched)


# ---------------------------------------------------------------------------
# Multi-match: every row within threshold, fixed width, priority-first
# ---------------------------------------------------------------------------

#: Effective multi-match threshold when ``threshold=None``: the largest f32
#: strictly below :data:`EXACT_MATCH_EPS`, so the uniform ``distance <=
#: threshold`` test means exactly ``distance < EXACT_MATCH_EPS`` — exact
#: matches only — for every representable f32 distance, analog sub-0.5
#: values included.
_EXACT_THR = float(np.nextafter(np.float32(EXACT_MATCH_EPS), np.float32(0)))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class AMMultiMatchResult:
    """Fixed-width multi-match outcome (a registered pytree).

    The TCAM answer shape: *all* rows at distance <= threshold, reported in
    a static-width window of ``M`` slots ordered by ascending (distance,
    row index) — so slot 0 is the **priority entry**, the classic CAM
    lowest-address-wins resolution (and, for a routing table stored
    longest-prefix-first, the longest matching prefix).  Non-match slots
    hold index ``-1`` / distance ``+inf`` / flags ``False``.

    ``match_count`` is the exact number of in-threshold rows — also when it
    exceeds ``M``, in which case ``overflow`` is set and the window holds
    the ``M`` highest-priority matches.  Per-query shapes are (Q, M) for the
    window fields and (Q,) for the counts; a single 1-D query drops the
    leading axis.
    """

    indices: jnp.ndarray      # int32 matching rows, priority-first; -1 empty
    distances: jnp.ndarray    # float32 distances; +inf on empty slots
    exact: jnp.ndarray        # bool — slot is an exact match (< EPS)
    matched: jnp.ndarray      # bool — slot holds a within-threshold match
    match_count: jnp.ndarray  # int32 — exact #rows within threshold
    overflow: jnp.ndarray     # bool — match_count > M (window truncated)

    def tree_flatten(self):
        """Flatten into the six result arrays (no aux data)."""
        return (self.indices, self.distances, self.exact, self.matched,
                self.match_count, self.overflow), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from the children of :meth:`tree_flatten`."""
        del aux
        return cls(*children)

    @property
    def single_match(self) -> jnp.ndarray:
        """(Q,) bool — exactly one row matched (the unambiguous-hit flag)."""
        return self.match_count == 1

    @property
    def multiple_match(self) -> jnp.ndarray:
        """(Q,) bool — more than one row matched."""
        return self.match_count > 1

    @property
    def priority_index(self) -> jnp.ndarray:
        """(Q,) the winning row — lowest (distance, index); -1 if no match."""
        return self.indices[..., 0]

    @property
    def priority_distance(self) -> jnp.ndarray:
        """(Q,) distance of the priority entry (+inf if no match)."""
        return self.distances[..., 0]


def _match_threshold(threshold, qn: int) -> jnp.ndarray:
    """Normalise a multi-match threshold to a (Q, 1) float32 array.

    ``None`` means exact matches only (:data:`_EXACT_THR`); scalars and
    per-query (Q,) / (Q, 1) arrays broadcast.
    """
    t = jnp.asarray(_EXACT_THR if threshold is None else threshold,
                    jnp.float32)
    if t.ndim == 0:
        t = t[None, None]
    else:
        t = t.reshape(-1, 1)
    return jnp.broadcast_to(t, (qn, 1))


def _finalize_matches(indices, distances, count, thr_q, matches: int,
                      squeeze: bool) -> AMMultiMatchResult:
    """Blank non-match slots and assemble an :class:`AMMultiMatchResult`.

    ``indices``/``distances`` are the (Q, M) lexicographic top-M (already
    padded to static width ``matches``); since every within-threshold row
    sorts before every out-of-threshold one, the first ``min(count, M)``
    slots are exactly the matches, in priority order.
    """
    matched = distances <= thr_q
    exact = matched & (distances < EXACT_MATCH_EPS)
    indices = jnp.where(matched, indices, -1)
    distances = jnp.where(matched, distances, jnp.inf)
    count = count.astype(jnp.int32)
    overflow = count > matches
    if squeeze:
        indices, distances = indices[0], distances[0]
        exact, matched = exact[0], matched[0]
        count, overflow = count[0], overflow[0]
    return AMMultiMatchResult(indices=indices, distances=distances,
                              exact=exact, matched=matched,
                              match_count=count, overflow=overflow)


def _care_kwargs(table: AMTable, be: _Backend) -> dict:
    """The ``care=`` kwarg for a masked table — or {} (and a clear error).

    Building ``{}`` for unmasked tables keeps every existing call site
    byte-identical: backends without the masked tier are still called with
    their original signature.
    """
    if table.care is None:
        return {}
    if not be.masked:
        raise ValueError(
            "table has a care mask but the backend lacks the 'masked' "
            f"capability tier (has {be.capabilities}); use a masked backend "
            "such as 'ref' or 'pallas'")
    return {"care": table.care}


def _prep_queries(table: AMTable, queries) -> tuple[jnp.ndarray, bool]:
    if table.n_rows == 0:
        raise ValueError(
            "cannot search an empty AMTable (0 rows) — append codes first")
    queries = jnp.asarray(queries, jnp.int32)
    squeeze = queries.ndim == 1
    if squeeze:
        queries = queries[None]
    if queries.ndim != 2:
        raise ValueError(
            f"queries must be (Q, D) or a single (D,) word, got a "
            f"{queries.ndim}-D array of shape {queries.shape} — flatten "
            f"leading batch axes before searching")
    if queries.shape[-1] != table.width:
        raise ValueError(
            f"query width {queries.shape[-1]} != stored width {table.width}")
    return queries, squeeze


def distances(table: AMTable, queries, *,
              backend: str | BackendFn | None = None) -> jnp.ndarray:
    """Full (Q, N) distance matrix (backend-native dtype, contract units).

    Always the dense tier — this function's whole point is the matrix.
    Tables with a care mask route it through (masked backends only).
    """
    queries, squeeze = _prep_queries(table, queries)
    be = _resolve_backend(backend)
    d = be.dense(queries, table.codes, table.bits, table.distance,
                 **_care_kwargs(table, be))
    return d[0] if squeeze else d


def search(table: AMTable, queries, *, k: int = 1,
           threshold: float | jnp.ndarray | None = None,
           backend: str | BackendFn | None = None,
           valid_rows: int | jnp.ndarray | None = None,
           matches: int | None = None):
    """Batched top-k / threshold / multi-match associative search.

    Args:
      table: the code store; passed as a pytree, so this function is jittable
        as a whole (``jax.jit(lambda t, q: am.search(t, q, k=4))``), vmaps
        over query batches, and runs inside ``shard_map`` bodies.  A table
        with a ``care`` plane (ternary cells) requires a backend with the
        ``"masked"`` capability.
      queries: (Q, D) — or a single (D,) — integer symbol words.
      k: how many nearest rows to return (static; clamped to the table size).
      threshold: optional match radius in contract units (may be traced);
        ``result.matched`` flags candidates with ``distance <= threshold``.
        ``None`` means exact-match-only flags.
      backend: registered backend name, a raw backend callable (dense tier),
        or ``None`` for the module default (``"ref"``).
      valid_rows: optional (possibly traced) count of live rows — rows at
        index >= ``valid_rows`` get distance ``+inf`` and can never rank.
        Lets a fixed-capacity table slab (``repro.serve.am_service``) vary
        its fill level without changing compiled shapes; when fewer than
        ``k`` rows are live, the surplus entries come back with ``+inf``
        distance and ``exact``/``matched`` False.
      matches: switch to **multi-match** mode with a static window width M:
        return *all* rows at distance <= ``threshold`` (exact matches only
        when ``threshold=None``) as an :class:`AMMultiMatchResult` — the
        first ``min(match_count, M)`` slots hold the matches in ascending
        (distance, row index) order, slot 0 being the lowest-index priority
        entry.  Mutually exclusive with ``k`` (leave ``k=1``).

    Returns:
      :class:`AMSearchResult` with rows ordered best-first — or, with
      ``matches=``, an :class:`AMMultiMatchResult`.  Ties break to the
      lowest row index (``jax.lax.top_k`` stability), which both the fused
      backend tier and the sharded path reproduce bitwise.

    Dispatch: when the backend registers a fused tier and ``k`` <=
    :data:`FUSED_K_MAX`, the top-k (and the ``valid_rows`` mask) runs inside
    the backend's kernel and the (Q, N) matrix is never materialised;
    otherwise the dense matrix + ``lax.top_k`` path runs.  The two are
    bitwise-identical by contract.  Multi-match needs the ``fused_count``
    extension (the in-kernel ``match_count``) to stay fused; other backends
    count on the dense matrix.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if matches is not None:
        if k != 1:
            raise ValueError(
                f"pass either k= or matches=, not both (k={k}, "
                f"matches={matches})")
        if matches < 1:
            raise ValueError(f"matches must be >= 1, got {matches}")
    queries, squeeze = _prep_queries(table, queries)
    be = _resolve_backend(backend)
    ckw = _care_kwargs(table, be)

    if matches is not None:
        m_eff = min(matches, table.n_rows)
        thr_q = _match_threshold(threshold, queries.shape[0])
        if (be.fused is not None and be.fused_count
                and 1 <= m_eff <= FUSED_K_MAX):
            idx, dist, count = be.fused(
                queries, table.codes, table.bits, table.distance, k=m_eff,
                valid_rows=valid_rows, count_le=thr_q, **ckw)
        else:
            if be.fused is not None and be.fused_count \
                    and m_eff > FUSED_K_MAX:
                _note_fused_fallback()
            d = be.dense(queries, table.codes, table.bits, table.distance,
                         **ckw).astype(jnp.float32)
            if valid_rows is not None:
                rows = jnp.arange(table.n_rows)
                d = jnp.where(rows[None, :] < valid_rows, d, jnp.inf)
            count = jnp.sum(d <= thr_q, axis=1).astype(jnp.int32)
            neg, idx = jax.lax.top_k(-d, m_eff)
            idx, dist = idx.astype(jnp.int32), -neg
        dist, idx = _pad_candidates(dist, idx, matches)
        return _finalize_matches(idx, dist, count, thr_q, matches, squeeze)

    k = min(k, table.n_rows)
    if be.fused is not None and 1 <= k <= FUSED_K_MAX:
        idx, dist = be.fused(queries, table.codes, table.bits, table.distance,
                             k=k, valid_rows=valid_rows, **ckw)
        return _finalize(idx, dist, threshold, squeeze)
    if be.fused is not None and k > FUSED_K_MAX:
        _note_fused_fallback()
    d = be.dense(queries, table.codes, table.bits, table.distance, **ckw)
    d = d.astype(jnp.float32)
    if valid_rows is not None:
        rows = jnp.arange(table.n_rows)
        d = jnp.where(rows[None, :] < valid_rows, d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    return _finalize(idx.astype(jnp.int32), -neg, threshold, squeeze)


# ---------------------------------------------------------------------------
# Sharded multi-bank search
# ---------------------------------------------------------------------------

#: Cross-bank merge strategies ``search_sharded`` accepts.
MERGE_STRATEGIES = ("auto", "allgather", "tree", "ring")

#: ``merge="auto"`` picks a collective merge (tree or ring) at and above
#: this ``model``-axis width.  Below it the flat all-gather's single
#: collective round beats any multi-round schedule's latency; above it the
#: all-gather's O(k * banks) per-device traffic dominates (ROADMAP: flat
#: merge stops scaling past ~16-way meshes).  ``docs/ARCHITECTURE.md``
#: holds the decision table; ``tests/test_docs_contract.py`` keeps the two
#: in sync.
TREE_MERGE_MIN_BANKS = 16

#: ``merge="auto"`` upgrades tree -> ring when ``k >= this * n_banks``.
#: The ring's per-device traffic is O(Q * k) independent of bank count
#: versus the tree's O(Q * k * log banks), but it pays 2*(banks - 1)
#: ppermute/all-gather rounds versus ceil(log2(banks)) + 1 — so it only
#: wins when the per-round payload is large enough that bandwidth, not
#: round latency, dominates, i.e. k >> banks.
RING_MERGE_MIN_K_PER_BANK = 4

#: Row-index sentinel for candidate-list padding and duplicate masking; sorts
#: after every real row index (and after +inf-masked real rows at equal
#: distance), so sentinels can never displace a genuine candidate.
_IDX_SENTINEL = np.iinfo(np.int32).max


def resolve_merge(merge: str, n_banks: int, k: int = 1) -> str:
    """Resolve a ``merge=`` argument to a concrete strategy.

    Args:
      merge: ``"auto"``, ``"allgather"``, ``"tree"`` or ``"ring"``.
      n_banks: width of the mesh axis the table is banked over.
      k: the top-k (or match window) width the merge will carry; only
        consulted by ``"auto"``, which upgrades tree -> ring in the
        bandwidth-bound regime ``k >= RING_MERGE_MIN_K_PER_BANK * n_banks``.

    Returns:
      ``"allgather"``, ``"tree"`` or ``"ring"`` (``"auto"`` resolves by
      :data:`TREE_MERGE_MIN_BANKS` then :data:`RING_MERGE_MIN_K_PER_BANK`).
    """
    if merge not in MERGE_STRATEGIES:
        raise ValueError(
            f"unknown merge {merge!r}; expected one of {MERGE_STRATEGIES}")
    if merge != "auto":
        return merge
    if n_banks < TREE_MERGE_MIN_BANKS:
        return "allgather"
    return "ring" if k >= RING_MERGE_MIN_K_PER_BANK * n_banks else "tree"


def _pad_candidates(dist: jnp.ndarray, idx: jnp.ndarray,
                    k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pad a (Q, k_local) candidate list out to (Q, k) with +inf sentinels.

    The tree merge exchanges fixed-width (Q, k) lists every round; a bank
    with fewer than k live candidates pads with (+inf, _IDX_SENTINEL)
    entries, which lexicographically rank after every genuine candidate —
    including +inf-masked real rows, whose indices are < _IDX_SENTINEL.
    """
    q, k_local = dist.shape
    if k_local >= k:
        return dist, idx
    pad = k - k_local
    return (jnp.concatenate(
                [dist, jnp.full((q, pad), jnp.inf, dist.dtype)], axis=1),
            jnp.concatenate(
                [idx, jnp.full((q, pad), _IDX_SENTINEL, idx.dtype)], axis=1))


def _lex_merge_topk(dist_a: jnp.ndarray, idx_a: jnp.ndarray,
                    dist_b: jnp.ndarray, idx_b: jnp.ndarray,
                    k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge two per-query candidate lists, keeping the lexicographic top-k.

    The order is ascending (distance, global row index) — ``lax.sort`` with
    two keys — which is exactly ``lax.top_k``'s tie-break over a dense
    matrix, so composing this merge up a reduction tree stays
    bitwise-identical to the single-device search.

    Duplicate candidates (same global row arriving from both lists, which
    happens on non-power-of-two bank counts where the recursive-doubling
    coverage wraps) are masked to (+inf, _IDX_SENTINEL) before the final
    cut, so a row can never occupy two of the k slots and displace the true
    k-th best.
    """
    dist = jnp.concatenate([dist_a, dist_b], axis=1)
    idx = jnp.concatenate([idx_a, idx_b], axis=1)
    dist, idx = jax.lax.sort((dist, idx), num_keys=2)
    # identical (distance, row) pairs are adjacent after the lex sort
    dup = jnp.concatenate(
        [jnp.zeros_like(idx[:, :1], dtype=bool), idx[:, 1:] == idx[:, :-1]],
        axis=1)
    dist = jnp.where(dup, jnp.inf, dist)
    idx = jnp.where(dup, _IDX_SENTINEL, idx)
    dist, idx = jax.lax.sort((dist, idx), num_keys=2)
    return dist[:, :k], idx[:, :k]


def _merge_bank_candidates(dist_local: jnp.ndarray, idx_local: jnp.ndarray, *,
                           axis: str, n_banks: int, k: int,
                           strategy: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Reduce per-bank (Q, k_local) candidates to the replicated global top-k.

    The cross-bank half of :func:`search_sharded`'s bank body, factored out
    so other banked layers (the set-associative index tier,
    :mod:`repro.index.ivf`) reuse the identical collective schedule.  Must
    run inside a ``shard_map`` body over mesh axis ``axis``; both inputs are
    this bank's candidate list, already (distance, global row index)-sorted
    with +inf for masked rows.

    Args:
      dist_local: (Q, k_local) float32 per-bank candidate distances.
      idx_local: (Q, k_local) int32 *global* row indices of the candidates.
      axis: the mesh axis name the table is banked over.
      n_banks: width of that axis.
      k: global top-k to keep (the exchanged lists are padded to it).
      strategy: ``"tree"``, ``"allgather"`` or ``"ring"`` (resolve
        ``"auto"`` first via :func:`resolve_merge`).

    Returns:
      ``(indices, distances)`` — the (Q, k) global top-k, replicated across
      the axis, ordered by ascending (distance, global row index).
    """
    if strategy == "ring":
        # Reduce-scatter over query chunks: the Q queries split into
        # n_banks chunks of ceil(Q/banks); in round r bank p forwards the
        # partially-merged chunk it accumulated last round and folds its
        # own local candidates into the chunk arriving from bank p-1.
        # After banks-1 rounds bank p holds chunk (p+1) % banks fully
        # merged (every bank's candidates folded in exactly once — no
        # duplicates, so the pairwise merge's dedup only ever fires on
        # sentinels), and one chunk-sized all-gather rebuilds the
        # replicated (Q, k) result.  Per-device traffic is
        # 2 * (banks-1) * (Q/banks) * k entries ~= O(Q * k), independent
        # of bank count — the bandwidth-optimal schedule for k >> banks —
        # at the price of 2*(banks-1) rounds of latency.
        dist_c, idx_c = _pad_candidates(dist_local, idx_local, k)
        q = dist_c.shape[0]
        chunk = -(-q // n_banks)
        pad_q = chunk * n_banks - q
        if pad_q:
            dist_c = jnp.pad(dist_c, ((0, pad_q), (0, 0)),
                             constant_values=jnp.inf)
            idx_c = jnp.pad(idx_c, ((0, pad_q), (0, 0)),
                            constant_values=_IDX_SENTINEL)
        p = jax.lax.axis_index(axis)

        def _local_chunk(c):
            return (jax.lax.dynamic_slice_in_dim(dist_c, c * chunk, chunk),
                    jax.lax.dynamic_slice_in_dim(idx_c, c * chunk, chunk))

        perm = [(i, (i + 1) % n_banks) for i in range(n_banks)]
        acc_d, acc_i = _local_chunk(p)
        for r in range(n_banks - 1):
            acc_d = jax.lax.ppermute(acc_d, axis, perm)
            acc_i = jax.lax.ppermute(acc_i, axis, perm)
            ld, li = _local_chunk((p - r - 1) % n_banks)
            acc_d, acc_i = _lex_merge_topk(acc_d, acc_i, ld, li, k)
        # bank p finished chunk (p+1) % banks: gathered[j] is chunk j+1,
        # so rolling by one restores query order before the un-pad.
        gd = jax.lax.all_gather(acc_d, axis)
        gi = jax.lax.all_gather(acc_i, axis)
        gd = jnp.roll(gd, 1, axis=0).reshape(chunk * n_banks, k)[:q]
        gi = jnp.roll(gi, 1, axis=0).reshape(chunk * n_banks, k)[:q]
        return gi, gd

    if strategy == "tree":
        # Recursive doubling: round r receives the running top-k of the
        # bank 2**r places down-ring and folds it in with the pairwise
        # lexicographic merge.  After ceil(log2(banks)) rounds every
        # bank has folded in every other bank's candidates (offsets
        # 0..2**rounds-1 cover the whole ring; overlap on
        # non-power-of-two widths is handled by the merge's dedup), so
        # the result is the replicated global top-k — per-device
        # traffic O(Q * k * log banks) instead of O(Q * k * banks).
        dist_c, idx_c = _pad_candidates(dist_local, idx_local, k)
        for r in range((n_banks - 1).bit_length()):
            shift = 1 << r
            perm = [(i, (i + shift) % n_banks) for i in range(n_banks)]
            dist_p = jax.lax.ppermute(dist_c, axis, perm)
            idx_p = jax.lax.ppermute(idx_c, axis, perm)
            dist_c, idx_c = _lex_merge_topk(dist_c, idx_c,
                                            dist_p, idx_p, k)
        return idx_c, dist_c

    # flat merge: all-gather every bank's candidates, re-rank locally with
    # the two-key (distance, global row index) sort.  A positional top_k
    # would only honour the tie-break contract when bank order equals
    # global-index order for equal distances — true for contiguously banked
    # rows, NOT for the set-associative index tier, where a bank's sets
    # hold arbitrary global ids.  The explicit lex sort is exact for both.
    dists = jax.lax.all_gather(dist_local, axis, axis=1, tiled=True)
    gis = jax.lax.all_gather(idx_local, axis, axis=1, tiled=True)
    dists, gis = jax.lax.sort((dists, gis), num_keys=2)
    return gis[:, :k], dists[:, :k]


def merge_traffic_bytes(n_banks: int, q: int, k: int, *, merge: str = "auto",
                        n_rows: int | None = None) -> int:
    """Per-device bytes *received* over the mesh axis during the merge.

    A traffic *model* kept next to the implementation it describes: the
    per-round tree payload comes from ``jax.eval_shape`` over
    :func:`_pad_candidates` — the same helper ``search_sharded``'s bank body
    builds its exchanged lists with — and the all-gather count multiplies
    out the local (Q, k_local) candidate avals.  If the bank body changes
    what it exchanges, change this function in the same commit;
    ``benchmarks/bench_am_topk.py`` asserts the O(k * log banks) tree bound
    against it.

    Args:
      n_banks: width of the banked mesh axis.
      q: query batch size per device.
      k: requested top-k.
      merge: strategy (``"auto"`` resolves by :func:`resolve_merge`).
      n_rows: total table rows; defaults to enough that every bank fields a
        full (Q, k) candidate list.

    Returns:
      Bytes received per device across all merge rounds.
    """
    if n_banks < 1:
        raise ValueError(f"n_banks must be >= 1, got {n_banks}")
    n_rows = n_banks * max(1, k) if n_rows is None else n_rows
    k_eff = min(k, n_rows)
    strategy = resolve_merge(merge, n_banks, k_eff)
    local_n = -(-n_rows // n_banks)
    k_local = min(k_eff, local_n)
    local = (jax.ShapeDtypeStruct((q, k_local), jnp.float32),
             jax.ShapeDtypeStruct((q, k_local), jnp.int32))

    def _nbytes(avals) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(avals))

    if strategy == "allgather":
        # every other bank's (Q, k_local) pair lands on this device
        return (n_banks - 1) * _nbytes(local)
    padded = jax.eval_shape(functools.partial(_pad_candidates, k=k_eff),
                            *local)
    if strategy == "ring":
        # reduce-scatter + all-gather, both moving one (ceil(Q/banks),
        # k_eff) chunk pair per round for banks-1 rounds each: ~2*Q*k_eff
        # entries received per device, independent of the bank count.
        chunk = -(-q // n_banks)
        payload = tuple(jax.ShapeDtypeStruct((chunk, a.shape[1]), a.dtype)
                        for a in padded)
        return 2 * (n_banks - 1) * _nbytes(payload)
    # tree: one padded (Q, k_eff) pair per recursive-doubling round
    rounds = (n_banks - 1).bit_length()        # == ceil(log2(n_banks))
    return rounds * _nbytes(padded)


def search_sharded(table: AMTable, queries, *, mesh, rules=None, k: int = 1,
                   threshold: float | jnp.ndarray | None = None,
                   backend: str | BackendFn | None = None,
                   valid_rows: int | jnp.ndarray | None = None,
                   merge: str = "auto", matches: int | None = None):
    """Row-partitioned search over the ``model`` mesh axis (multi-bank merge).

    The table is split into ``mesh.shape[rules.tp]`` banks
    (:meth:`repro.dist.specs.Rules.am_table`); each bank runs the backend on
    its rows and keeps a local top-k with *global* row indices, then the
    per-bank candidates are reduced to the global top-k by the selected
    merge strategy — the paper's multi-bank match-merge.

    Args:
      table: the code store (searched in full by every query).
      queries: (Q, D) — or a single (D,) — integer symbol words.
      k: how many nearest rows to return (static; clamped to the table size).
      threshold: optional match radius, :func:`search` semantics.
      backend: registered backend name / raw dense callable / ``None``.
      valid_rows: optional live-row count, :func:`search` semantics — rows at
        index >= ``valid_rows`` are masked to ``+inf`` in every bank (the
        capacity-slab serving path routes here unchanged when the service
        holds a mesh).
      mesh: the device mesh; its ``rules.tp`` axis is the bank axis.
      rules: optional :class:`repro.dist.specs.Rules`; defaults to
        ``make_rules(mesh, "tp")``.
      merge: cross-bank candidate reduction — ``"allgather"`` (one tiled
        all-gather round, O(k * banks) per-device traffic), ``"tree"``
        (ceil(log2(banks)) ``ppermute`` rounds of pairwise lexicographic
        merge, O(k * log banks) traffic), ``"ring"`` (a banks-round
        reduce-scatter over query chunks plus one chunk all-gather,
        O(Q * k) traffic independent of bank count — the bandwidth-optimal
        schedule for k >> banks), or ``"auto"`` (allgather below
        :data:`TREE_MERGE_MIN_BANKS` banks, then ring when ``k >=``
        :data:`RING_MERGE_MIN_K_PER_BANK` ``* banks``, else tree).  Any
        bank count works with every strategy, including 1 and
        non-powers-of-two.
      matches: multi-match mode, :func:`search` semantics.  Per-bank
        fixed-width candidate windows ride the very same contract-3 merge as
        top-k; per-bank within-threshold counts are ``psum``-reduced over
        the bank axis, so ``match_count`` is the exact global count and
        ``overflow = match_count > M`` subsumes an OR of per-bank overflow
        flags (a bank-local overflow implies the global count exceeds M).
        Both merge topologies produce identical results.

    Returns:
      :class:`AMSearchResult` — or :class:`AMMultiMatchResult` with
      ``matches=`` — bitwise-identical to :func:`search` on one
      device for every merge strategy: per-bank candidate lists are each
      ordered by (distance, global row index) and both merges resolve ties
      to the lowest global row index exactly like the single-device
      ``top_k``.  This holds for any backend that is a pure row-wise
      function of its ``codes`` argument — backends whose output depends on
      the table's shape or global row position (e.g.
      :func:`make_analog_backend` with a ``variation_key``, which samples
      noise from ``codes.shape``) are not supported here.

    Data-parallel query sharding composes automatically: when ``rules`` has
    data-parallel axes (a (dp, model) mesh) and the query count divides
    their total width, queries go in sharded by
    :meth:`~repro.dist.specs.Rules.am_queries_dp` — each data shard searches
    only its own query slice against all banks, instead of every device
    redundantly searching the full replicated batch.  Results are identical
    either way; the dp path just removes the replicated compute and memory.

    Fused-tier backends run their streaming top-k kernel *per bank* (the
    bank's slice of the ``valid_rows`` mask handled in-kernel), so each
    device moves only O(Q*k_local) candidate bytes into the merge whichever
    tier the backend has.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist import specs as dist_specs

    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if matches is not None:
        if k != 1:
            raise ValueError(
                f"pass either k= or matches=, not both (k={k}, "
                f"matches={matches})")
        if matches < 1:
            raise ValueError(f"matches must be >= 1, got {matches}")
    rules = rules or dist_specs.make_rules(mesh, "tp")
    axis = rules.tp
    n_banks = mesh.shape[axis]
    queries, squeeze = _prep_queries(table, queries)
    be = _resolve_backend(backend)
    if table.care is not None:
        _care_kwargs(table, be)         # masked-capability check (raises)
    bits, distance_mode = table.bits, table.distance

    n = table.n_rows
    k_eff = min(matches if matches is not None else k, n)
    strategy = resolve_merge(merge, n_banks, k_eff)
    pad = (-n) % n_banks
    codes = jnp.pad(table.codes, ((0, pad), (0, 0)))
    # padded care rows are all-don't-care (0), but like padded codes rows
    # they sit at index >= n >= valid_rows and are masked to +inf anyway
    care = (None if table.care is None
            else jnp.pad(table.care, ((0, pad), (0, 0))))
    local_n = (n + pad) // n_banks
    k_local = min(k_eff, local_n)
    vr = jnp.asarray(n if valid_rows is None else valid_rows, jnp.int32)
    use_fused = (be.fused is not None and 1 <= k_local <= FUSED_K_MAX
                 and (matches is None or be.fused_count))
    if (be.fused is not None and k_local > FUSED_K_MAX
            and (matches is None or be.fused_count)):
        _note_fused_fallback()
    thr_q = (None if matches is None
             else _match_threshold(threshold, queries.shape[0]))

    # data-parallel query sharding: each dp shard searches its own slice
    dp_axes = tuple(rules.dp or ())
    dp_width = 1
    for a in dp_axes:
        dp_width *= mesh.shape.get(a, 1)
    shard_queries = dp_width > 1 and queries.shape[0] % dp_width == 0
    q_spec = rules.am_queries_dp() if shard_queries else rules.am_queries()
    out_batch = rules.dp if shard_queries else None

    def _bank_body(codes_local, q, vr, *extra):
        """Per-bank local top-k + the cross-bank candidate merge."""
        it = iter(extra)
        care_local = next(it) if care is not None else None
        thr_l = next(it) if matches is not None else None
        ckw = {} if care_local is None else {"care": care_local}
        base = jax.lax.axis_index(axis) * local_n
        cl = None
        if use_fused:
            # the bank's slice of the global live-row mask, applied in-kernel
            vr_local = jnp.clip(vr - base, 0, local_n)
            if matches is not None:
                il, dl, cl = be.fused(q, codes_local, bits, distance_mode,
                                      k=k_local, valid_rows=vr_local,
                                      count_le=thr_l, **ckw)
            else:
                il, dl = be.fused(q, codes_local, bits, distance_mode,
                                  k=k_local, valid_rows=vr_local, **ckw)
        else:
            d = be.dense(q, codes_local, bits, distance_mode,
                         **ckw).astype(jnp.float32)
            row = base + jnp.arange(local_n)
            d = jnp.where(row[None, :] < vr, d, jnp.inf)  # mask dead/pad rows
            if matches is not None:
                cl = jnp.sum(d <= thr_l, axis=1).astype(jnp.int32)
            neg, il = jax.lax.top_k(-d, k_local)
            dl = -neg
        gi = (il + base).astype(jnp.int32)
        gi, dl = _merge_bank_candidates(dl, gi, axis=axis, n_banks=n_banks,
                                        k=k_eff, strategy=strategy)
        if matches is None:
            return gi, dl
        # exact global match count: each bank counted disjoint rows
        return gi, dl, jax.lax.psum(cl, axis)

    # Outputs are replicated over `model` by construction (every merge ends
    # with each bank holding the same candidates).  The replication check
    # cannot follow that through a pallas_call or the merge, so it is off.
    args = [codes, queries, vr]
    in_specs = [rules.am_table(), q_spec, P()]
    out_specs = [P(out_batch, None), P(out_batch, None)]
    if care is not None:
        args.append(care)
        in_specs.append(rules.am_table())
    if matches is not None:
        args.append(thr_q)
        in_specs.append(q_spec)
        out_specs.append(P(out_batch))
    out = jax.shard_map(
        _bank_body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False)(*args)
    if matches is None:
        idx, dist = out
        return _finalize(idx, dist, threshold, squeeze)
    idx, dist, count = out
    dist, idx = _pad_candidates(dist, idx, matches)
    return _finalize_matches(idx, dist, count, thr_q, matches, squeeze)
