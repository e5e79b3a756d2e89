"""Production serving API for associative search — the CAM as a service.

The paper positions SEE-MCAM as an associative-search engine fronting ML
inference; this module is that engine's serving surface.  An
:class:`AMService` sits beside the LM :class:`repro.serve.engine.Engine` /
:class:`repro.serve.scheduler.ContinuousBatcher` and is the one sanctioned
way to run ``am.search`` under traffic:

  >>> svc = AMService()
  >>> svc.create_table("responses", width=256, bits=3, capacity=4096,
  ...                  policy="lru", backend="pallas")
  >>> svc.append("responses", codes, values=payloads)
  >>> fut = svc.submit("responses", query, k=4)        # queues, non-blocking
  >>> resp = fut.result()                              # flushes the batch
  >>> resp.hit, resp.value, resp.indices, resp.distances

Design — why this never compiles or syncs per request:

* **Fixed-capacity slabs.**  Each named table is an :class:`am.AMTable`
  whose ``codes`` array is allocated at ``capacity`` rows once; the live
  row count ``n`` is passed to ``am.search(..., valid_rows=n)`` as a traced
  scalar, so appends and evictions never change compiled shapes.
* **Micro-batched dispatch.**  ``submit`` queues; ``flush`` coalesces queued
  lookups by (table, k, backend, thresholded?) signature, pads each group's
  query count to the next power of two, and issues ONE jitted search per
  group.  Compilation count is exactly one per padding-bucket signature
  (exposed as ``stats()["compilations"]``); results come back in ONE
  ``jax.device_get`` per group — no per-request ``bool()``/``int()`` syncs.
* **Pipelined dispatch driver.**  Dispatch and readback are two stages:
  ``_launch_group`` issues the compiled search (JAX dispatch is
  asynchronous — the host returns immediately) and records an in-flight
  group; the completion stage (``_resolve_group``) performs the single
  ``jax.device_get`` per group and fans results out to the waiting
  :class:`PendingSearch` futures.  The synchronous :meth:`AMService.flush`
  runs the two stages back to back (the bitwise reference path, always
  available to single-request callers); an :class:`AMDriver` — a background
  thread, or an explicit event-loop object stepped with
  :meth:`AMDriver.run_once` for deterministic tests — overlaps them: up to
  ``max_in_flight`` dispatched groups compute on device while the host
  batches the next bucket, and in-flight groups retire strictly in dispatch
  order (FIFO).  The driver owns the flush deadline outright, replacing the
  cooperative ``poll()`` whose logical-clock variant could never fire under
  idle traffic.
* **Appends overlap in-flight searches.**  A dispatched group snapshots the
  table (pytree), its payload list, and its ``version`` at launch; appends
  and evictions replace ``_TableState.table`` without disturbing the
  snapshot, and the group's LRU-touch meta is written back at completion
  only if the version is unchanged (a racing append/evict wins and the
  stale touch is dropped — LRU maintenance is best-effort under overlap,
  exact under the synchronous path).  ``append()`` therefore never blocks
  on an in-flight search's device buffers.
* **Admission control.**  Per-table QPS token buckets (``qps_budget``, with
  ``burst``) and queued-lookup caps (``max_queue``) bound what one hot
  table can queue, so it cannot starve a shared flush.  The per-table
  ``admission`` knob picks the over-budget behaviour — ``"reject"`` raises
  :class:`AdmissionError`, ``"shed"`` resolves the lookup immediately as a
  non-admitted miss (``SearchResponse.admitted`` False), ``"block"`` waits
  for headroom.  Counters surface through ``stats()`` (queue depth,
  in-flight groups, rejected/shed/blocked, cumulative queue wait).
* **Cross-request dedup.**  Identical (query, threshold) rows inside one
  flush group are dispatched once and the shared result row fans out to
  every duplicate — under Zipfian traffic most of a wave is repeats, so
  this shrinks both the dispatched batch (often into a smaller padding
  bucket) and the readback.  ``stats()["dedup_hits"]`` counts the rows
  saved; ``stats()["dedup_rate"]`` is the saved fraction of dispatched
  lookups.
* **Fused search dispatch.**  The compiled dispatch calls ``am.search`` /
  ``am.search_sharded``, which route to the backend's *fused* top-k tier
  when it has one (``"pallas"`` does): the (Q, N) distance matrix is never
  materialised and the slab's live-row mask is applied in-kernel.  Same
  signature, same compile accounting — the tiering is invisible here.
* **Sub-linear tables via the index tier.**  ``create_table(...,
  index=IndexSpec(sets=32, probes=4))`` gives a table a set-associative
  :class:`repro.index.ivf.IVFIndex`: built lazily once the table holds
  ``index.build_threshold`` live rows, extended incrementally on appends,
  rebuilt after compaction (eviction renumbers rows).  Dispatches route
  through ``repro.index.ivf.search`` transparently — same micro-batching,
  same padding buckets, same compile accounting (the index is a traced
  pytree argument; only slab-capacity growth recompiles) — and
  ``stats()["index"]`` reports probe counts and candidate fractions.
  ``probes == sets`` is bitwise the flat search; fewer probes trade
  certified recall for O(S + probes * N/S) work per lookup.
* **Ternary tables and multi-match lookups.**  ``create_table(...,
  ternary=True)`` allocates a care-mask plane beside the code slab (a
  masked-capable backend required); ``append(..., care=)`` writes per-row
  don't-care patterns (omitted rows default to all-care, i.e. plain
  exact-match rows), and compaction carries the care plane with its rows.
  ``submit(..., matches=M)`` switches a lookup to TCAM multi-match
  semantics — all rows within threshold in an M-wide (distance, row)-ordered
  window plus exact ``match_count``/``overflow`` — through the same jitted
  bucket dispatch (``matches`` joins the group signature), same padding
  buckets, same compile accounting.  Indexed tables refuse ``matches=``
  (the coarse pass prunes rows multi-match must see) and refuse
  ``ternary`` (a wildcard row belongs to no single set).
* **Eviction is part of the API.**  ``AMTable.meta`` carries (insert,
  last-hit) timestamps (:data:`am.META_INSERT` / :data:`am.META_LAST_HIT`).
  Exact hits update last-hit *inside* the compiled dispatch via
  :func:`am.touch`; ``"lru"`` tables evict the least-recently-hit rows on
  overflow, ``"ttl"`` tables expire rows older than ``ttl`` (falling back
  to FIFO on overflow), ``"reject"`` tables raise :class:`TableFullError`.
  A table can therefore never exceed its configured capacity.
* **Pluggable placement.**  Constructed with a ``mesh`` (and optionally
  :class:`repro.dist.specs.Rules`), the same dispatch routes through
  ``am.search_sharded`` — rows banked over the ``model`` axis via
  ``Rules.am_table()``, query batches dp-sharded through
  ``Rules.am_queries_dp()`` when the bucket divides the mesh's data axes,
  meta kept replicated per ``Rules.am_meta()`` — with identical results.
  The ``merge=`` knob picks the cross-bank candidate reduction
  (``"allgather"`` | ``"tree"`` | ``"ring"`` | ``"auto"``, see
  ``am.search_sharded``);
  it is baked into the service's compiled dispatch, so switching topology
  never changes the dispatch signature or the compile accounting.

Clock semantics — which features need which clock:

The service reads time through one injected ``time_fn``.  With
``time_fn=None`` the clock is **logical**: it advances by exactly one tick
per ``submit`` / ``append`` / ``flush``, which makes every eviction and
deadline decision deterministic and replayable — the right default for
tests and offline replay.  With ``time_fn=time.monotonic`` (or any fake
callable — deterministic driver tests inject one) the clock is **wall**:
readings are re-based to the service's first observation so float32 meta
stays integer-exact.

* ``ttl`` eviction and LRU ordering work under either clock (ages are
  clock-unit differences).
* ``flush_after`` **as an idle deadline requires a real clock**: under the
  logical clock the deadline is only ever observed at submit time (each
  submit ages the queue by one tick), so a half-full bucket with no further
  submits would wait forever — the constructor warns about exactly this
  combination.  :meth:`AMService.poll` and :class:`AMDriver` both read the
  clock without advancing it; they can only make progress on a clock that
  advances on its own.
* A **background** :class:`AMDriver` (:meth:`AMService.start_driver`)
  refuses to own a ``flush_after`` deadline without a real clock; an
  unstarted driver stepped by hand (``AMDriver(svc).run_once(now=...)``)
  accepts explicit ``now`` values, which is how the deterministic tests
  drive deadlines.
* ``qps_budget`` token buckets refill from clock deltas, so under the
  logical clock every submit — admitted or not — advances the tick: a
  budget then means "sustained lookups per submit-tick", and an exhausted
  bucket refills as over-budget traffic keeps arriving (were the clock
  frozen on non-admitted submits, ``reject``/``shed`` would livelock at
  zero tokens forever).  ``admission="block"`` still requires a real
  clock and raises without one.

Latency control: ``max_batch`` caps how many lookups queue before an
automatic dispatch, and ``flush_after`` is a deadline (in clock units) on
the oldest queued request — enforced at every submit, by the driver's loop,
and by the legacy :meth:`AMService.poll` hook for loops that poll by hand.

Profiler spans: the service's stages run inside ``jax.profiler``
``TraceAnnotation`` spans, on the clock the device trace uses, so a trace
shows which stage the host was in while the device idled.  With no
profiler running a span only checks that none is.

* ``am.append`` — the public :meth:`AMService.append` under the lock
  (``table``, ``rows``);
* ``am.make_room`` — eviction before an append: the policy, any
  compaction, and a readback of the policy's meta column only where the
  policy can evict, ``ttl`` always and ``lru`` at capacity; ``reject`` and
  ``lru`` below capacity decide from the fill alone (``rows``: live rows
  before);
* ``am.write`` — each slab write (``slab``: codes, meta or care);
* ``am.launch`` — one group's dedup, padding and dispatch enqueue
  (``group``: the service's sequence number of the group, ``lookups``,
  ``bucket``);
* ``am.resolve`` — one group's completion stage, and inside it
  ``am.readback`` around ``jax.device_get`` (``group``);
* ``am.driver.wait`` — the background driver idle between steps.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import warnings
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding

from repro.core import am
from repro.dist import specs as dist_specs
from repro.index import ivf
from repro.index.ivf import IndexSpec

#: Eviction policies a table may be created with.
POLICIES = ("lru", "ttl", "reject")

#: Admission-control behaviours for an over-budget submit (``create_table``'s
#: ``admission=`` knob); the docs/ARCHITECTURE.md admission table is asserted
#: against this tuple.
ADMISSION_MODES = ("reject", "shed", "block")

#: Lifecycle states of an :class:`AMDriver`; the docs/ARCHITECTURE.md driver
#: state table is asserted against this tuple (in this order).
DRIVER_STATES = ("idle", "running", "draining", "stopped")

#: In-flight groups retire strictly in dispatch order.  The contract test
#: keeps docs/ARCHITECTURE.md's completion-ordering statement tied to this.
COMPLETION_ORDER = "fifo"

#: Meta timestamps are float32, which is integer-exact only to 2**24; the
#: logical clock rebases every live timestamp down once it reaches this, so
#: LRU/TTL ordering stays exact for arbitrarily long-running services.
_REBASE_TICKS = float(1 << 23)


class TableFullError(RuntimeError):
    """An append would exceed capacity and the policy forbids eviction."""


class AdmissionError(RuntimeError):
    """A submit was refused by admission control (budget or queue cap)."""


# ---------------------------------------------------------------------------
# Request / response dataclasses
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SearchRequest:
    """One associative lookup against a named table."""

    rid: int
    table: str
    query: np.ndarray              # (D,) int32 symbol word
    k: int = 1
    threshold: float | None = None
    backend: str | None = None     # None -> the table's default backend
    matches: int | None = None     # multi-match window width (TCAM mode)
    submitted_at: float = 0.0


@dataclasses.dataclass(frozen=True)
class SearchResponse:
    """Top-k outcome of one request, resolved to its host payload.

    All arrays are host numpy, produced by the single per-batch readback.
    Entries beyond the table's live row count carry index ``-1``, distance
    ``+inf`` and False flags.  ``admitted`` is False only for lookups shed
    by admission control (``admission="shed"``), which never reach a
    dispatch and resolve as misses.
    """

    rid: int
    table: str
    indices: np.ndarray            # (k,) int32 rows, best first; -1 invalid
    distances: np.ndarray          # (k,) float32 contract units
    exact: np.ndarray              # (k,) bool — exact word match
    matched: np.ndarray            # (k,) bool — within the request threshold
    value: Any = None              # payload of the best row on an exact hit
    admitted: bool = True          # False: shed by admission control
    match_count: int | None = None  # multi-match only: total matching rows
    overflow: bool | None = None    # multi-match only: count > window width

    @property
    def hit(self) -> bool:
        """Did the best candidate match exactly?"""
        return bool(self.exact[0])

    @property
    def best_row(self) -> int:
        return int(self.indices[0])


class PendingSearch:
    """Future-like handle returned by :meth:`AMService.submit`.

    ``result()`` forces progress if the response has not been produced yet:
    with no driver running it flushes the service's queue (single-request
    callers stay synchronous while concurrent callers get coalesced into
    one dispatch); with a live :class:`AMDriver` it expedites the queued
    bucket and waits on the driver's completion stage.
    """

    __slots__ = ("request", "_service", "_response", "_event")

    def __init__(self, service: "AMService", request: SearchRequest):
        self.request = request
        self._service = service
        self._response: SearchResponse | None = None
        self._event = threading.Event()

    @property
    def done(self) -> bool:
        return self._response is not None

    def _resolve(self, response: SearchResponse) -> None:
        self._response = response
        self._event.set()

    def result(self, timeout: float | None = None) -> SearchResponse:
        if self._response is None:
            svc = self._service
            drv = svc._driver
            if drv is not None and drv.is_alive():
                svc._expedite(self)
                deadline = (None if timeout is None
                            else time.monotonic() + timeout)
                while self._response is None:
                    if drv.exception is not None:
                        raise RuntimeError(
                            "AMService driver thread died") from drv.exception
                    if not drv.is_alive():
                        svc.flush()            # driver gone: finish sync
                        break
                    wait = 0.05
                    if deadline is not None:
                        wait = min(wait, deadline - time.monotonic())
                        if wait <= 0:
                            raise TimeoutError(
                                f"request {self.request.rid} unresolved "
                                f"after {timeout}s")
                    self._event.wait(wait)
            else:
                svc.flush()
            # A concurrent flush() may have claimed this request's bucket
            # and be mid-readback: our own flush was then a no-op.  Every
            # claimed future is guaranteed to resolve (vanished tables
            # resolve as misses), so wait for that completion stage.
            if self._response is None and not self._event.wait(timeout):
                raise TimeoutError(
                    f"request {self.request.rid} unresolved after {timeout}s")
        return self._response


# ---------------------------------------------------------------------------
# Table state
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _TableState:
    """One named table: capacity slab + host-side bookkeeping."""

    name: str
    table: am.AMTable              # (capacity, D) codes + (capacity, 2) meta
    n: int                         # live rows (<= capacity)
    capacity: int
    policy: str
    ttl: float | None
    backend: str
    values: list                   # host payloads, aligned with live rows
    version: int = 0               # bumped on every append/delete/evict
    appends: int = 0
    evicted: int = 0
    meta_readbacks: int = 0        # _make_room calls that read meta back
    hits: int = 0
    misses: int = 0
    # -- admission control ---------------------------------------------------
    qps_budget: float | None = None    # sustained lookups per clock unit
    burst: float = 1.0                 # token-bucket depth
    max_queue: int | None = None       # cap on this table's queued lookups
    admission: str = "reject"          # over-budget behaviour
    tokens: float = 0.0                # current token-bucket level
    tokens_at: float = 0.0             # clock reading of the last refill
    queued: int = 0                    # lookups currently in the shared queue
    rejected: int = 0
    shed: int = 0
    blocked: int = 0                   # submits that had to wait
    # -- set-associative index tier (repro.index) ----------------------------
    index_spec: IndexSpec | None = None
    index: "ivf.IVFIndex | None" = None   # built lazily per index_spec
    index_builds: int = 0              # full (re)builds (lazy + compaction)
    index_lookups: int = 0             # lookups served through the index
    index_groups: int = 0              # dispatched groups served through it
    index_frac_sum: float = 0.0        # sum of per-group candidate fractions


@dataclasses.dataclass
class _InFlightGroup:
    """One dispatched bucket awaiting its completion-stage readback.

    Everything needed to resolve the futures is snapshotted at launch:
    device arrays from the compiled dispatch, the payload list *reference*
    (appends only extend it, compaction rebinds a fresh list — either way
    the snapshot stays aligned with the dispatched row indices), and the
    table version guarding the deferred LRU-touch meta writeback.
    """

    table: _TableState
    futs: list
    slot_of: list
    arrays: tuple                  # (idx, dist, exact, matched, count,
    #                                 overflow) on device; the last two are
    #                                 None unless the group is multi-match
    new_meta: Any                  # post-touch meta, written back if fresh
    version: int                   # table.version at launch
    values: list                   # payload list as of launch
    now: float                     # dispatch-time clock reading
    seq: int                       # the service's sequence number of it
    index_frac: Any = None         # device scalar: mean candidate fraction
    #                                (None when the dispatch was unindexed)

    def ready(self) -> bool:
        """True when every result array has landed (non-blocking probe)."""
        return all(getattr(a, "is_ready", lambda: True)()
                   for a in self.arrays)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _auto_axes(mesh):
    """``mesh`` with every axis Auto, whatever axis types it came with.

    ``jax.make_mesh`` gives Explicit axes by default, which type-check
    every update of a banked slab against the slab's sharding: an append of
    m rows would have to divide evenly over the banks.  The service owns
    its slabs' layout, so it runs on Auto axes, where the compiler places
    a write of any row count.
    """
    auto = (jax.sharding.AxisType.Auto,) * len(mesh.axis_names)
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


@partial(jax.jit, static_argnames=("layout",))
def _write_rows(slab, rows, start, *, layout=None):
    """``slab`` with ``rows`` written from row ``start``.

    ``layout`` (the slab's own sharding on a service mesh) pins the result's
    layout, so a banked slab stays banked whatever the write's size; without
    the pin the partitioner chooses the result's layout itself.
    """
    out = jax.lax.dynamic_update_slice(slab, rows.astype(slab.dtype),
                                       (start, 0))
    if layout is None:
        return out
    return jax.lax.with_sharding_constraint(out, layout)


@partial(jax.jit, static_argnames=("col",))
def _meta_column(meta, *, col):
    """Column ``col`` of the whole ``(capacity, 2)`` meta slab.

    Its shape is the slab's, never the live row count's, so it compiles
    once per capacity and layout; the caller slices the live rows on the
    host.
    """
    return meta[:, col]


# ---------------------------------------------------------------------------
# The service
# ---------------------------------------------------------------------------

class AMService:
    """Named associative-search tables + a micro-batching lookup scheduler.

    Thread-safe: every public method may be called from any thread; a
    single service lock guards table state, the queue and the in-flight
    list, while device readbacks happen outside it (the completion stage).

    Args:
      mesh: optional device mesh — when given, every dispatch routes through
        :func:`am.search_sharded` (rows banked over ``rules.tp``).
      rules: optional :class:`repro.dist.specs.Rules`; defaults to
        ``make_rules(mesh, "tp")`` when a mesh is given.
      merge: cross-bank merge strategy forwarded to ``am.search_sharded``
        (``"auto"`` | ``"allgather"`` | ``"tree"`` | ``"ring"``); only
        meaningful with a mesh.
      max_batch: queued lookups that trigger an automatic flush.
      flush_after: deadline in clock units — the queue is dispatched when
        the oldest queued request has waited at least this long.  As an
        *idle* deadline (no further submits arriving) this needs a clock
        that advances on its own: construct with ``time_fn`` and run an
        :class:`AMDriver` (or call :meth:`poll` from a loop).  Setting it
        with the default logical clock warns — see the module docstring's
        clock-semantics section.
      time_fn: clock source; ``None`` uses a deterministic logical tick
        (+1.0 per submit/append/flush).
    """

    def __init__(self, *, mesh=None, rules=None, merge: str = "auto",
                 max_batch: int = 64, flush_after: float | None = None,
                 time_fn: Callable[[], float] | None = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if merge not in am.MERGE_STRATEGIES:
            raise ValueError(f"unknown merge {merge!r}; expected one of "
                             f"{am.MERGE_STRATEGIES}")
        if flush_after is not None and time_fn is None:
            warnings.warn(
                "AMService(flush_after=...) with the default logical clock "
                "only observes the deadline at submit time: an idle "
                "half-full bucket never auto-flushes (the clock advances "
                "only on submit/append/flush, so poll() and drivers see a "
                "frozen queue age).  Pass time_fn=time.monotonic and run "
                "svc.start_driver() — or inject a fake clock in tests — "
                "for a live idle deadline.", RuntimeWarning, stacklevel=2)
        self._mesh = None if mesh is None else _auto_axes(mesh)
        self._merge = merge
        self._rules = (rules or dist_specs.make_rules(self._mesh, "tp")) \
            if mesh is not None else rules
        self.max_batch = max_batch
        self.flush_after = flush_after
        self._time_fn = time_fn
        self._clock = 0.0
        self._epoch: float | None = None
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._tables: dict[str, _TableState] = {}
        self._pending: list[PendingSearch] = []
        self._in_flight: collections.deque[_InFlightGroup] = \
            collections.deque()
        self._drain_req = False
        self._resolving = 0            # popped in-flight groups mid-readback
        self._driver: AMDriver | None = None
        self._next_rid = 0
        self.flushes = 0
        self.readbacks = 0
        self.dispatched = 0            # requests routed through a dispatch
        self.queue_wait_s = 0.0        # of those, summed launch - submit
        self._group_seq = 0            # dispatch groups launched
        self.dedup_hits = 0            # of those, resolved from a shared row
        self.fused_fallbacks = 0       # groups dense-downgraded by k ceiling
        self._dispatch = self._build_dispatch()

    # -- clock ---------------------------------------------------------------

    def _tick(self) -> float:
        # Timestamps land in float32 meta, so they must stay small: wall
        # clocks are re-based to the service's first reading, and the
        # logical clock shifts every live timestamp down before it leaves
        # float32's integer-exact range (old rows go negative, which
        # preserves both LRU order and TTL ages).  Rebase only when nothing
        # is queued or in flight: a deferred meta writeback computed before
        # the shift must never land on shifted meta.
        if self._time_fn is not None:
            return self._now()
        self._clock += 1.0
        if (self._clock >= _REBASE_TICKS and not self._pending
                and not self._in_flight and not self._resolving):
            shift = self._clock
            self._clock = 0.0
            for t in self._tables.values():
                t.table = dataclasses.replace(t.table,
                                              meta=t.table.meta - shift)
        return self._clock

    def _now(self) -> float:
        """Read the clock without advancing the logical tick.

        ``poll()`` and the driver use this so an idle loop observes
        deadlines instead of creating them (every logical tick ages the
        queue by one unit, which would make N no-op polls flush any queue).
        """
        if self._time_fn is not None:
            t = float(self._time_fn())
            if self._epoch is None:
                self._epoch = t
            return t - self._epoch
        return self._clock

    # -- table lifecycle -----------------------------------------------------

    def create_table(self, name: str, *, width: int, bits: int = 3,
                     distance: str = "hamming", capacity: int = 1024,
                     policy: str = "lru", ttl: float | None = None,
                     backend: str = "ref",
                     qps_budget: float | None = None,
                     burst: float | None = None,
                     max_queue: int | None = None,
                     admission: str = "reject",
                     index: IndexSpec | None = None,
                     ternary: bool = False) -> None:
        """Allocate an empty capacity-bounded table under ``name``.

        Admission control (all optional): ``qps_budget`` is a sustained
        lookups-per-clock-unit token bucket (bucket depth ``burst``,
        default ``max(1, qps_budget)``), ``max_queue`` caps this table's
        queued lookups, and ``admission`` picks the over-budget behaviour
        (one of :data:`ADMISSION_MODES`).

        ``index`` (an :class:`repro.index.IndexSpec`) turns on the
        set-associative index tier for this table: once the table holds
        ``index.build_threshold`` live rows, dispatches route through
        :func:`repro.index.ivf.search` (or its sharded variant on a mesh)
        with the spec's ``probes`` — transparently, same signatures, same
        compile accounting; results follow the search contract exactly,
        with sub-linear work at ``probes < sets``.  Appends extend the
        index incrementally; evictions/deletes rebuild it (compaction
        renumbers rows).  ``stats()`` grows an ``"index"`` block.

        ``ternary`` allocates a per-row care-mask plane alongside the code
        slab (all-ones for rows appended without an explicit ``care=``, so
        binary rows in a ternary table behave exactly like a plain table's).
        Requires a backend with the ``"masked"`` capability tier and is
        mutually exclusive with ``index`` (the coarse pass has no wildcard
        semantics — a don't-care row belongs to no single set).
        """
        if name in self._tables:
            raise ValueError(f"table {name!r} already exists")
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if policy not in POLICIES:
            raise ValueError(f"unknown policy {policy!r}; expected {POLICIES}")
        if (ttl is None) == (policy == "ttl"):
            raise ValueError("ttl must be set iff policy == 'ttl'")
        if admission not in ADMISSION_MODES:
            raise ValueError(f"unknown admission {admission!r}; expected "
                             f"one of {ADMISSION_MODES}")
        if qps_budget is not None and qps_budget <= 0:
            raise ValueError(f"qps_budget must be > 0, got {qps_budget}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if index is not None:
            index.validate()
            if index.sets > capacity:
                raise ValueError(
                    f"index sets ({index.sets}) exceeds table capacity "
                    f"({capacity}); every set needs at least one row slot")
        am.get_backend(backend)          # fail fast on unknown backends
        if ternary:
            if index is not None:
                raise ValueError(
                    "ternary tables cannot use the index tier: the "
                    "set-associative coarse pass has no wildcard semantics")
            if "masked" not in am.backend_capabilities(backend):
                raise ValueError(
                    f"backend {backend!r} lacks the 'masked' capability "
                    "tier required for ternary tables")
        table = am.make_table(
            self._place(jnp.zeros((capacity, width), jnp.int32), rows=True),
            bits=bits, distance=distance,
            meta=self._place(am.serving_meta(capacity, 0.0), rows=False),
            care_mask=(self._place(jnp.ones((capacity, width), jnp.int32),
                                   rows=True)
                       if ternary else None))
        if burst is None:
            burst = max(1.0, float(qps_budget)) if qps_budget else 1.0
        else:
            burst = float(burst)
        with self._lock:
            self._tables[name] = _TableState(
                name=name, table=table, n=0, capacity=capacity, policy=policy,
                ttl=ttl, backend=backend, values=[],
                qps_budget=qps_budget, burst=burst, max_queue=max_queue,
                admission=admission, tokens=burst, tokens_at=self._now(),
                index_spec=index)

    def _place(self, slab, *, rows: bool):
        """Lay a fresh capacity slab out on the service's mesh, if any.

        Row slabs (codes, care) bank over ``rules.tp`` per
        :meth:`Rules.am_table` when the capacity divides evenly over the
        banks, so each device holds only its bank; meta, and row slabs that
        do not divide, replicate.  Appends and compaction keep the layout.
        """
        if self._mesh is None:
            return slab
        banks = self._mesh.shape[self._rules.tp]
        spec = (self._rules.am_table() if rows and slab.shape[0] % banks == 0
                else self._rules.am_meta())
        return jax.device_put(slab, NamedSharding(self._mesh, spec))

    def _write(self, slab, rows, start: int, *, name: str):
        """``slab`` (named ``name``) with ``rows`` written from ``start``, in
        its own layout."""
        with TraceAnnotation("am.write", slab=name):
            layout = slab.sharding if self._mesh is not None else None
            return _write_rows(slab, jnp.asarray(rows), start, layout=layout)

    def drop_table(self, name: str) -> None:
        """Remove a table; queued and in-flight lookups resolve first.

        No future is ever lost: lookups still queued for the table are
        dispatched, and groups already in flight hold their own snapshot of
        the table state, so they complete normally even after removal.  The
        has-work check and the removal happen under one lock acquisition,
        so a submit racing this call either lands before the delete (and is
        flushed by the next loop pass) or fails with "unknown table" after
        it — never in between.
        """
        while True:
            with self._lock:
                self._state(name)        # fail fast on unknown names
                has_work = (any(p.request.table == name
                                for p in self._pending)
                            or any(g.table.name == name
                                   for g in self._in_flight))
                if not has_work:
                    del self._tables[name]
                    return
            self.flush()

    def _state(self, name: str) -> _TableState:
        try:
            return self._tables[name]
        except KeyError:
            raise ValueError(
                f"unknown table {name!r}; existing: {tuple(self._tables)}"
            ) from None

    def append(self, name: str, codes, values=None, *,
               care=None, now: float | None = None) -> None:
        """Insert rows (evicting per policy first if capacity requires).

        ``values`` carries one host payload per appended row (any object);
        payloads follow their rows through eviction and come back on exact
        hits as ``SearchResponse.value``.  Appends overlap in-flight
        searches: dispatched groups snapshot the table at launch, so this
        never blocks on a pending readback.

        ``care`` (ternary tables only) gives each appended row its
        care-mask plane, same shape as ``codes``; omitted, ternary rows
        default to all-care (plain exact-match rows).  Passing ``care``
        to a non-ternary table raises — create the table with
        ``ternary=True`` first.
        """
        codes = np.asarray(codes, np.int32)
        if codes.ndim == 1:
            codes = codes[None]
        with self._lock, TraceAnnotation("am.append", table=name,
                                         rows=codes.shape[0]):
            t = self._state(name)
            if codes.ndim != 2 or codes.shape[1] != t.table.width:
                raise ValueError(f"append codes shape {codes.shape} != "
                                 f"(m, {t.table.width})")
            if care is not None and t.table.care is None:
                raise ValueError(
                    f"table {name!r} is not ternary; create it with "
                    "ternary=True to append care masks")
            if t.table.care is not None:
                care = (np.ones_like(codes) if care is None
                        else np.asarray(care, np.int32))
                if care.ndim == 1:
                    care = care[None]
                if care.shape != codes.shape:
                    raise ValueError(f"append care shape {care.shape} != "
                                     f"codes shape {codes.shape}")
            m = codes.shape[0]
            if m > t.capacity:
                raise TableFullError(
                    f"appending {m} rows exceeds table capacity {t.capacity}")
            if values is None:
                values = [None] * m
            elif not isinstance(values, (list, tuple)):
                values = [values]
            if len(values) != m:
                raise ValueError(f"{len(values)} values for {m} rows")
            now = self._tick() if now is None else float(now)
            self._make_room(t, m, now)
            start = t.n
            t.table = dataclasses.replace(
                t.table,
                codes=self._write(t.table.codes, codes, t.n, name="codes"),
                meta=self._write(t.table.meta, am.serving_meta(m, now), t.n,
                                 name="meta"),
                care=(t.table.care if t.table.care is None else
                      self._write(t.table.care,
                                  (care != 0).astype(np.int32), t.n,
                                  name="care")))
            t.values.extend(values)
            t.n += m
            t.appends += m
            t.version += 1
            if t.index is not None:
                # incremental: new rows land at their sets' slab ends with
                # the global ids the slab write just gave them
                t.index = ivf.append(t.index, codes, start_row=start)
            elif t.index_spec is not None:
                self._rebuild_index(t)       # lazy build once big enough

    def delete(self, name: str, rows) -> int:
        """Drop live rows by index array or boolean mask; returns the count.

        Integer indices must satisfy ``0 <= row < live rows``: a negative
        index would numpy-wrap onto the *wrong live row* (silently killing
        it and desyncing the payload alignment), so both out-of-range
        directions raise :class:`ValueError` naming the offenders.
        """
        with self._lock:
            t = self._state(name)
            rows = np.asarray(rows)
            kill = np.zeros((t.n,), bool)
            if rows.dtype == np.bool_:
                if rows.shape != (t.n,):
                    raise ValueError(f"mask shape {rows.shape} != ({t.n},)")
                kill |= rows
            else:
                idx = rows.reshape(-1).astype(np.int64)
                bad = idx[(idx < 0) | (idx >= t.n)]
                if bad.size:
                    raise ValueError(
                        f"delete indices out of range [0, {t.n}): "
                        f"{sorted(set(bad.tolist()))}")
                kill[idx] = True
            killed = int(kill.sum())
            if killed:
                self._compact(t, kill)
            return killed

    def evict(self, name: str, *, now: float | None = None) -> int:
        """Run the table's eviction policy now; returns rows evicted.

        For ``"ttl"`` tables this expires rows older than ``ttl``; for
        ``"lru"``/``"reject"`` it is a no-op unless the table somehow
        exceeds capacity (it cannot through this API).
        """
        with self._lock:
            t = self._state(name)
            now = self._tick() if now is None else float(now)
            before = t.n
            self._make_room(t, 0, now)
            return before - t.n

    def _make_room(self, t: _TableState, m: int, now: float) -> None:
        """Evict per policy so ``m`` more rows fit under ``capacity``.

        Only ``ttl`` (expiry, always) and ``lru`` at capacity read meta
        back, and then only the one column the policy orders by; ``reject``
        and ``lru`` below capacity decide from the fill alone.
        """
        with TraceAnnotation("am.make_room", rows=t.n):
            if t.n == 0:
                return
            overflow = t.n + m - t.capacity
            if t.policy != "ttl" and overflow <= 0:
                return
            if t.policy == "reject":
                raise TableFullError(
                    f"table {t.name!r} is full ({t.capacity} rows) and "
                    f"policy 'reject' forbids eviction")
            # lru: least-recently-hit first; ttl: expiry by insert age,
            # then oldest insert first on overflow
            col = am.META_LAST_HIT if t.policy == "lru" else am.META_INSERT
            stamps = np.asarray(_meta_column(t.table.meta, col=col))[:t.n]
            t.meta_readbacks += 1
            kill = np.zeros((t.n,), bool)
            if t.policy == "ttl":
                kill |= (now - stamps) > t.ttl
                overflow -= int(kill.sum())
            if overflow > 0:
                alive = np.flatnonzero(~kill)
                order = alive[np.argsort(stamps[alive], kind="stable")]
                kill[order[:overflow]] = True
            if kill.any():
                t.evicted += int(kill.sum())
                self._compact(t, kill)

    def _compact(self, t: _TableState, kill: np.ndarray) -> None:
        """Delete masked live rows and repack survivors at the slab front."""
        live = am.AMTable(codes=t.table.codes[:t.n], meta=t.table.meta[:t.n],
                          care=(None if t.table.care is None
                                else t.table.care[:t.n]),
                          bits=t.table.bits, distance=t.table.distance)
        live = am.delete(live, kill)               # the eviction-mask path
        keep = np.flatnonzero(~kill)
        t.table = dataclasses.replace(
            t.table,
            codes=self._write(jnp.zeros_like(t.table.codes), live.codes, 0,
                              name="codes"),
            meta=self._write(jnp.zeros_like(t.table.meta), live.meta, 0,
                             name="meta"),
            care=(t.table.care if t.table.care is None else
                  self._write(jnp.ones_like(t.table.care), live.care, 0,
                              name="care")))
        t.values = [t.values[i] for i in keep]
        t.n = live.n_rows
        t.version += 1
        if t.index_spec is not None:
            # compaction renumbered the surviving rows: the index's global
            # ids are stale, so rebuild (or drop below the build threshold)
            self._rebuild_index(t)

    def _rebuild_index(self, t: _TableState) -> None:
        """Lock held: (re)build the table's IVF index per its spec.

        Below the spec's ``build_threshold`` the index is dropped instead —
        dispatches fall back to the exact flat search until the table grows
        back (training centroids on a handful of rows is pure noise).
        """
        spec = t.index_spec
        if spec is None:
            return
        if t.n < spec.build_threshold:
            t.index = None
            return
        live = am.AMTable(codes=t.table.codes[:t.n], bits=t.table.bits,
                          distance=t.table.distance)
        t.index = ivf.build(live, sets=spec.sets, method=spec.method,
                            seed=spec.seed, iters=spec.iters)
        t.index_builds += 1

    # -- admission -----------------------------------------------------------

    def _admission_verdict(self, t: _TableState,
                           now: float) -> str | None:
        """Refill the token bucket; return None (admit) or what's exceeded."""
        if t.max_queue is not None and t.queued >= t.max_queue:
            return "max_queue"
        if t.qps_budget is not None:
            t.tokens = min(t.burst,
                           t.tokens + (now - t.tokens_at) * t.qps_budget)
            t.tokens_at = now
            if t.tokens < 1.0:
                return "qps_budget"
        return None

    # -- lookups -------------------------------------------------------------

    def submit(self, name: str, query, *, k: int = 1,
               threshold: float | None = None,
               backend: str | None = None,
               matches: int | None = None) -> PendingSearch:
        """Queue one lookup; returns a handle whose ``result()`` blocks.

        Lookups against an empty table resolve immediately as misses —
        the cache-front pattern needs no special casing.  Admission control
        (when configured on the table) runs before anything queues.

        ``matches=M`` switches this lookup to TCAM multi-match semantics:
        the response carries *all* rows at distance <= ``threshold``
        (``threshold=None`` — exact matches only) in an M-wide window
        ordered by ascending (distance, row index), plus ``match_count``
        and ``overflow``.  Mutually exclusive with ``k`` and unavailable on
        indexed tables (the coarse pass prunes rows multi-match must see).
        """
        if matches is not None:
            if k != 1:
                raise ValueError("pass either k= or matches=, not both")
            if matches < 1:
                raise ValueError(f"matches must be >= 1, got {matches}")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        query = np.asarray(query, np.int32)
        if backend is not None:
            am.get_backend(backend)      # fail here, not at dispatch time
        blocked_once = False
        while True:
            with self._lock:
                t = self._state(name)
                if query.shape != (t.table.width,):
                    raise ValueError(
                        f"query shape {query.shape} != ({t.table.width},)")
                if matches is not None and t.index_spec is not None:
                    raise ValueError(
                        f"table {name!r} uses the index tier; multi-match "
                        "needs the full row scan (matches= is unavailable)")
                if (t.table.care is not None and backend is not None
                        and "masked" not in am.backend_capabilities(backend)):
                    raise ValueError(
                        f"backend {backend!r} lacks the 'masked' tier "
                        f"required by ternary table {name!r}")
                over = self._admission_verdict(t, self._now())
                if over is None:
                    if t.qps_budget is not None:
                        t.tokens -= 1.0
                    now = self._tick()
                    req = SearchRequest(
                        rid=self._next_rid, table=name, query=query,
                        k=min(k, t.capacity),
                        threshold=(None if threshold is None
                                   else float(threshold)),
                        backend=backend or t.backend, matches=matches,
                        submitted_at=now)
                    self._next_rid += 1
                    fut = PendingSearch(self, req)
                    if t.n == 0:
                        self._resolve_empty(t, fut)
                        return fut
                    self._pending.append(fut)
                    t.queued += 1
                    due = (len(self._pending) >= self.max_batch
                           or self._deadline_due(now))
                    drv = self._driver
                    if drv is not None and drv.is_alive():
                        if due:
                            drv._wake.set()   # the driver owns the dispatch
                        return fut
                    if not due:
                        return fut
                    break                     # sync path: flush outside loop
                # over budget: reject / shed / block.  Non-admitted submits
                # still advance the logical clock: the token bucket refills
                # from clock deltas, so a frozen clock would livelock an
                # exhausted budget (shed/reject forever, no refill).
                if self._time_fn is None and t.admission != "block":
                    self._tick()
                if t.admission == "reject":
                    t.rejected += 1
                    raise AdmissionError(
                        f"table {name!r} over {over} "
                        f"(admission='reject'): lookup refused")
                if t.admission == "shed":
                    t.shed += 1
                    req = SearchRequest(
                        rid=self._next_rid, table=name, query=query,
                        k=min(k, t.capacity),
                        threshold=(None if threshold is None
                                   else float(threshold)),
                        backend=backend or t.backend, matches=matches,
                        submitted_at=self._now())
                    self._next_rid += 1
                    fut = PendingSearch(self, req)
                    fut._resolve(self._miss_response(req, admitted=False))
                    return fut
                # block: wait for headroom outside the lock
                if not blocked_once:
                    t.blocked += 1
                    blocked_once = True
                drv = self._driver
                queue_over = over == "max_queue"
            if queue_over:
                self.flush()                  # make room ourselves
                continue
            if self._time_fn is None:
                raise AdmissionError(
                    f"table {name!r} over qps_budget with admission='block' "
                    "but no real clock to wait on: construct AMService with "
                    "time_fn=time.monotonic, or use 'reject'/'shed'")
            if drv is not None and drv.is_alive():
                drv._wake.set()
            time.sleep(5e-4)
        self.flush()
        return fut

    def lookup(self, name: str, query, *, k: int = 1,
               threshold: float | None = None,
               backend: str | None = None,
               matches: int | None = None) -> SearchResponse:
        """Synchronous convenience: submit + flush in one call."""
        return self.submit(name, query, k=k, threshold=threshold,
                           backend=backend, matches=matches).result()

    @staticmethod
    def _miss_response(req: SearchRequest, *,
                       admitted: bool = True) -> SearchResponse:
        mm = req.matches is not None
        k = req.matches if mm else req.k
        return SearchResponse(
            rid=req.rid, table=req.table,
            indices=np.full((k,), -1, np.int32),
            distances=np.full((k,), np.inf, np.float32),
            exact=np.zeros((k,), bool), matched=np.zeros((k,), bool),
            admitted=admitted,
            match_count=0 if mm else None, overflow=False if mm else None)

    def _resolve_empty(self, t: _TableState, fut: PendingSearch) -> None:
        fut._resolve(self._miss_response(fut.request))
        t.misses += 1

    def _deadline_due(self, now: float) -> bool:
        """Lock held: has the oldest queued request crossed ``flush_after``?"""
        return (self.flush_after is not None and bool(self._pending)
                and now - self._pending[0].request.submitted_at
                >= self.flush_after)

    def _take_pending(self) -> dict[tuple, list[PendingSearch]]:
        """Lock held: drain the queue into signature groups.

        Lookups whose table has vanished (dropped between queueing and this
        drain) resolve immediately as misses instead of raising — a flush
        must never orphan a drained future.
        """
        pending, self._pending = self._pending, []
        groups: dict[tuple, list[PendingSearch]] = {}
        for fut in pending:
            r = fut.request
            t = self._tables.get(r.table)
            if t is None:
                fut._resolve(self._miss_response(r))
                continue
            t.queued -= 1
            key = (r.table, r.k, r.backend, r.threshold is not None,
                   r.matches)
            groups.setdefault(key, []).append(fut)
        return groups

    def flush(self, *, now: float | None = None) -> int:
        """Dispatch and complete every queued lookup; returns how many.

        Requests are grouped by (table, k, backend, thresholded) signature;
        each group becomes one compiled ``am.search`` over queries padded to
        the next power of two, and one ``jax.device_get`` fans the batch
        back out to the waiting futures.  Every launched group goes through
        the in-flight list, so concurrent callers (``result()``, another
        ``flush``, a driver) can help retire it; groups already in flight
        are retired first (FIFO).  Single-threaded — or with no driver and
        no concurrent submitters — nothing is pending or in flight when
        this returns; under a live driver or concurrent submits new work
        may land at any moment, so use :meth:`drain` for a quiescence
        guarantee.  This serial launch-then-complete path is the bitwise
        reference the pipelined driver is tested against.
        """
        with self._lock:
            served = 0
            if self._pending:
                now = self._tick() if now is None else float(now)
                served = self._launch_pending(now)
        while self._complete_next():           # retire everything in flight
            pass
        return served

    def poll(self, *, now: float | None = None) -> int:
        """Flush the queue if the oldest queued request's deadline expired.

        The cooperative fallback for serve loops that poll by hand instead
        of running an :class:`AMDriver`: ``flush_after`` is otherwise only
        checked inside :meth:`submit`, so a half-full bucket would wait
        forever when no further submits arrive.  Reads the clock without
        advancing the logical tick, so polling is free when nothing is due
        — which also means that under the default logical clock an idle
        queue's age never changes and this can only fire via an explicit
        ``now=`` (the constructor warns about that combination).  Returns
        the number of lookups served.
        """
        with self._lock:
            if not self._pending or self.flush_after is None:
                return 0
            now = self._now() if now is None else float(now)
            if not self._deadline_due(now):
                return 0
        return self.flush(now=now)

    def drain(self, timeout: float | None = None) -> bool:
        """Resolve everything queued and in flight; True when fully drained.

        With a live driver this hands the work to it and waits on the
        completion stage; otherwise it is a synchronous :meth:`flush` plus
        a wait for any group a concurrent caller popped for readback —
        ``True`` is only returned once every drained future has resolved.
        """
        quiet = lambda: (not self._pending and not self._in_flight
                         and self._resolving == 0)
        drv = self._driver
        if drv is None or not drv.is_alive():
            self.flush()
            with self._cv:
                ok = self._cv.wait_for(
                    lambda: self._resolving == 0, timeout)
                return ok and quiet()
        with self._cv:
            self._drain_req = True
            drv._wake.set()
            ok = self._cv.wait_for(quiet, timeout)
            self._drain_req = False
        return ok

    # -- durability (repro.serve.snapshot) -----------------------------------

    def snapshot(self, directory, *, step: int | None = None,
                 keep: int = 2, app: dict | None = None,
                 drain_timeout: float | None = 60.0) -> int:
        """Durable snapshot of every table under ``directory``; returns step.

        Quiesces via :meth:`drain` first (a driver-consistent cut: every
        acknowledged append is included), then commits one atomic
        checkpoint per table plus a ``service.json`` commit point — see
        :mod:`repro.serve.snapshot` for the layout and manifest contract.
        """
        from repro.serve import snapshot as _snap
        return _snap.snapshot_service(self, directory, step=step, keep=keep,
                                      app=app, drain_timeout=drain_timeout)

    @classmethod
    def restore(cls, directory, *, mesh=None, rules=None,
                step: int | None = None, time_fn=None,
                merge: str | None = None, max_batch: int | None = None,
                flush_after: float | None = None) -> "AMService":
        """Warm-restart a service from a :meth:`snapshot` directory.

        ``mesh`` may have a *different* bank count than the snapshotting
        service (elastic reshard: row slabs re-bank through
        ``Rules.am_state()`` specs, searches stay bitwise-identical).
        """
        from repro.serve import snapshot as _snap
        return _snap.restore_service(directory, mesh=mesh, rules=rules,
                                     step=step, time_fn=time_fn, merge=merge,
                                     max_batch=max_batch,
                                     flush_after=flush_after)

    def _expedite(self, fut: PendingSearch) -> None:
        """Force progress for one future: dispatch its bucket, help retire.

        Called by ``result()`` under a live driver so a caller never waits
        out a distant deadline: anything queued launches now, and this
        thread helps the completion stage until the future resolves or the
        in-flight list empties (the driver may retire the final group).
        """
        with self._lock:
            if fut._response is not None:
                return
            if self._pending:
                self._launch_pending(self._tick())
        while fut._response is None and self._complete_next():
            pass

    # -- the two pipeline stages ---------------------------------------------

    def _launch_pending(self, now: float) -> int:
        """Lock held: dispatch every queued lookup as in-flight groups.

        The driver-side counterpart of :meth:`flush`'s launch phase —
        groups go onto the in-flight list for the completion stage instead
        of being read back inline.  Returns the number of lookups launched.
        """
        groups = self._take_pending()
        served = 0
        for (name, k, backend, has_thr, matches), futs in groups.items():
            self._launch_group(self._state(name), futs, k, backend, has_thr,
                               matches, now)
            served += len(futs)
        if served:
            self.flushes += 1
        return served

    def _launch_group(self, t: _TableState, futs: list[PendingSearch],
                      k: int, backend: str, has_thr: bool,
                      matches: int | None, now: float) -> _InFlightGroup:
        """Lock held: issue one compiled dispatch; no host sync happens here.

        Cross-request dedup: identical (query, threshold) rows dispatch
        once; the shared result row fans out to every duplicate at
        completion.  Hashing happens BEFORE padding, so a wave of repeats
        can collapse into a smaller power-of-two bucket.
        """
        seq, self._group_seq = self._group_seq, self._group_seq + 1
        with TraceAnnotation("am.launch", group=seq,
                             lookups=len(futs)) as span:
            slot_of: list[int] = []
            slots: dict[tuple[bytes, float | None], int] = {}
            uniq: list[PendingSearch] = []
            for fut in futs:
                r = fut.request
                key = (r.query.tobytes(), r.threshold)
                slot = slots.setdefault(key, len(slots))
                if slot == len(uniq):
                    uniq.append(fut)
                slot_of.append(slot)
            q = len(uniq)
            self.dispatched += len(futs)
            self.queue_wait_s += sum(now - fut.request.submitted_at
                                     for fut in futs)
            self.dedup_hits += len(futs) - q
            # Host-side mirror of am.fused_fallbacks(): the compiled
            # dispatch silently takes the dense O(Q*N) path when the
            # request's window exceeds am.FUSED_K_MAX even though the
            # backend has a fused tier.  The trace-time counter in am only
            # ticks once per compile; this one ticks per launched group, so
            # saturation is visible in stats().
            be = am._resolve_backend(t.backend)
            k_eff = min(matches if matches is not None else k,
                        t.table.n_rows)
            if (be.fused is not None and k_eff > am.FUSED_K_MAX
                    and (matches is None or be.fused_count)):
                self.fused_fallbacks += 1
            qb = _next_pow2(q)
            span.set_metadata(bucket=qb)
            queries = np.zeros((qb, t.table.width), np.int32)
            for i, fut in enumerate(uniq):
                queries[i] = fut.request.query
            thr = None
            if has_thr:
                tv = np.zeros((qb,), np.float32)
                tv[:q] = [fut.request.threshold for fut in uniq]
                thr = jnp.asarray(tv)
            args, kw = self._dispatch_args(t, queries, q, thr, now, k=k,
                                           backend=backend, matches=matches)
            idx, dist, exact, matched, count, overflow, new_meta, frac = \
                self._dispatch(*args, **kw)
            g = _InFlightGroup(table=t, futs=futs, slot_of=slot_of,
                               arrays=(idx, dist, exact, matched, count,
                                       overflow),
                               new_meta=new_meta, version=t.version,
                               values=t.values, now=now, seq=seq,
                               index_frac=frac)
            self._in_flight.append(g)
            return g

    def _dispatch_args(self, t: _TableState, queries, q: int, thr,
                       now: float, *, k: int, backend: str,
                       matches: int | None) -> tuple[tuple, dict]:
        """Positional and static arguments of one :attr:`_dispatch` call."""
        indexed = t.index is not None
        args = (t.table, t.index, jnp.asarray(queries),
                jnp.asarray(t.n, jnp.int32), jnp.asarray(q, jnp.int32), thr,
                jnp.asarray(now, jnp.float32))
        kw = dict(k=k, backend=backend, sharded=self._mesh is not None,
                  indexed=indexed,
                  probes=t.index_spec.probes if indexed else 0,
                  matches=matches)
        return args, kw

    def lower(self, name: str, *, batch: int = 1, k: int = 1,
              matches: int | None = None) -> jax.stages.Lowered:
        """The dispatch a group of ``batch`` lookups on ``name`` would run.

        Returned lowered, not run: ``.compile().as_text()`` shows what the
        device executes (a Pallas kernel appears as ``tpu_custom_call``),
        ``.compile().memory_analysis()`` its footprint.  ``k`` and
        ``matches`` are :meth:`submit`'s; the lookups carry no threshold and
        use the table's backend.
        """
        with self._lock:
            t = self._state(name)
            queries = np.zeros((_next_pow2(batch), t.table.width), np.int32)
            args, kw = self._dispatch_args(
                t, queries, batch, None, self._now(), k=min(k, t.capacity),
                backend=t.backend, matches=matches)
            return self._dispatch.lower(*args, **kw)

    def live_table(self, name: str) -> am.AMTable:
        """The live rows of ``name`` as an :class:`am.AMTable` snapshot.

        Rows appear in the order lookups report them, so
        ``am.search(svc.live_table(name), queries, k=k)`` answers what a
        lookup against the service answers (for ``k`` up to the live row
        count).
        """
        with self._lock:
            t = self._state(name)
            tb = t.table
            return am.AMTable(
                codes=tb.codes[:t.n], meta=tb.meta[:t.n],
                care=None if tb.care is None else tb.care[:t.n],
                bits=tb.bits, distance=tb.distance)

    def _complete_next(self, *, only_ready: bool = False) -> bool:
        """Retire the oldest in-flight group (FIFO); False if none retired.

        ``only_ready`` makes this a non-blocking probe: the group is
        skipped unless its device arrays have already landed.  A popped
        group counts in ``_resolving`` until its futures are resolved, so
        :meth:`drain` never declares quiescence mid-readback.
        """
        with self._lock:
            if not self._in_flight:
                return False
            g = self._in_flight[0]
            if only_ready and not g.ready():
                return False
            self._in_flight.popleft()
            self._resolving += 1
        try:
            with TraceAnnotation("am.resolve", group=g.seq):
                self._resolve_group(g)
        finally:
            with self._cv:
                self._resolving -= 1
                self._cv.notify_all()
        return True

    def _resolve_group(self, g: _InFlightGroup) -> None:
        """Completion stage: the single host sync for one dispatched group.

        ``jax.device_get`` (which blocks until the arrays are ready) runs
        OUTSIDE the service lock, so submits and appends proceed while a
        readback is in progress.  The deferred LRU-touch meta lands only if
        the table version is unchanged since launch — a racing append or
        eviction wins and the stale touch is dropped.
        """
        with TraceAnnotation("am.readback", group=g.seq):
            (idx, dist, exact, matched, count, overflow), frac = \
                jax.device_get((g.arrays, g.index_frac))
        with self._cv:
            t = g.table
            if self._tables.get(t.name) is t and t.version == g.version:
                t.table = dataclasses.replace(t.table, meta=g.new_meta)
            if frac is not None:
                t.index_lookups += len(g.futs)
                t.index_groups += 1
                t.index_frac_sum += float(frac)
            self.readbacks += 1
            for fut, slot in zip(g.futs, g.slot_of):
                hit = bool(exact[slot, 0])
                if hit:
                    t.hits += 1
                else:
                    t.misses += 1
                fut._resolve(SearchResponse(
                    rid=fut.request.rid, table=t.name, indices=idx[slot],
                    distances=dist[slot], exact=exact[slot],
                    matched=matched[slot],
                    value=g.values[int(idx[slot, 0])] if hit else None,
                    match_count=(None if count is None
                                 else int(count[slot])),
                    overflow=(None if overflow is None
                              else bool(overflow[slot]))))
            self._cv.notify_all()

    # -- driver lifecycle ----------------------------------------------------

    def start_driver(self, *, max_in_flight: int = 2,
                     poll_interval: float = 1e-3) -> "AMDriver":
        """Start a background :class:`AMDriver` thread; returns it.

        The driver owns the flush deadline, so ``flush_after`` requires a
        real clock here — a deadline against the logical clock can never
        fire from a background thread (nothing ticks it).
        """
        if self._driver is not None and self._driver.is_alive():
            raise RuntimeError("a driver is already running")
        if self.flush_after is not None and self._time_fn is None:
            raise ValueError(
                "a background driver cannot own a flush_after deadline on "
                "the logical clock (it never advances between submits); "
                "construct AMService with time_fn=time.monotonic")
        drv = AMDriver(self, max_in_flight=max_in_flight,
                       poll_interval=poll_interval)
        self._driver = drv
        drv.start()
        return drv

    def stop_driver(self, *, drain: bool = True,
                    timeout: float = 10.0) -> "AMDriver | None":
        """Stop the background driver (draining first by default)."""
        drv, self._driver = self._driver, None
        if drv is not None:
            drv.stop(drain=drain, timeout=timeout)
        return drv

    def close(self) -> None:
        """Drain and stop any running driver; the sync path stays usable."""
        self.stop_driver(drain=True)

    def _build_dispatch(self):
        """One jitted search dispatch per service (its own compile cache)."""
        mesh, rules, merge = self._mesh, self._rules, self._merge

        @partial(jax.jit,
                 static_argnames=("k", "backend", "sharded", "indexed",
                                  "probes", "matches"))
        def dispatch(table, index, queries, n_valid, q_valid, thresholds,
                     now, *, k, backend, sharded, indexed, probes,
                     matches=None):
            thr = None if thresholds is None else thresholds[:, None]
            frac = count = overflow = None
            if matches is not None:
                # TCAM multi-match: every row at distance <= threshold in a
                # fixed M-wide window (ascending (distance, row)), exact
                # counts and overflow — ternary tables pass their care plane
                # through am.search's masked tier untouched here
                if sharded:
                    res = am.search_sharded(
                        table, queries, mesh=mesh, rules=rules,
                        matches=matches, threshold=thr, backend=backend,
                        valid_rows=n_valid, merge=merge)
                else:
                    res = am.search(table, queries, matches=matches,
                                    threshold=thr, backend=backend,
                                    valid_rows=n_valid)
                count, overflow = res.match_count, res.overflow
            elif indexed:
                # the set-associative tier: coarse-rank centroids, fine
                # search only the probed sets' slabs.  The index holds
                # exactly the live rows, so no valid_rows is needed.
                if sharded:
                    r = ivf.search_sharded(
                        index, queries, mesh=mesh, rules=rules, k=k,
                        probes=probes, threshold=thr, backend=backend,
                        merge=merge)
                else:
                    r = ivf.search(index, queries, k=k, probes=probes,
                                   threshold=thr, backend=backend)
                res = r.result
                live_q = jnp.arange(queries.shape[0]) < q_valid
                frac = (jnp.sum(jnp.where(live_q, r.candidate_fraction, 0.0))
                        / jnp.maximum(q_valid, 1)).astype(jnp.float32)
            elif sharded:
                res = am.search_sharded(
                    table, queries, mesh=mesh, rules=rules, k=k,
                    threshold=thr, backend=backend, valid_rows=n_valid,
                    merge=merge)
            else:
                res = am.search(table, queries, k=k, threshold=thr,
                                backend=backend, valid_rows=n_valid)
            # LRU maintenance inside the compiled step: exact best-row hits
            # of real (non-padding) queries get their last-hit stamped
            # (the multi-match priority slot plays best-row's role)
            q_live = jnp.arange(queries.shape[0]) < q_valid
            top = (res.priority_index if matches is not None
                   else res.best_row)
            hit_rows = jnp.where(q_live & res.exact[:, 0], top,
                                 table.n_rows)       # n_rows == OOB sentinel
            meta = am.touch(table, hit_rows, now).meta
            if rules is not None:
                meta = dist_specs.constrain(meta, rules.am_meta())
            idx = jnp.where(jnp.isfinite(res.distances), res.indices, -1)
            dist, exact, matched = res.distances, res.exact, res.matched
            kw = idx.shape[1]
            want = k if matches is None else matches
            if kw < want:
                # an indexed search clamps k to its total slab capacity,
                # which can sit below a partially filled table's capacity;
                # pad back out so the response contract width holds
                pad = ((0, 0), (0, want - kw))
                idx = jnp.pad(idx, pad, constant_values=-1)
                dist = jnp.pad(dist, pad, constant_values=jnp.inf)
                exact = jnp.pad(exact, pad)
                matched = jnp.pad(matched, pad)
            return idx, dist, exact, matched, count, overflow, meta, frac

        return dispatch

    # -- stats ---------------------------------------------------------------

    def stats(self, name: str | None = None) -> dict:
        """Service-level (or one table's) observability counters.

        ``queue_wait_s`` is cumulative: the sum, over every lookup launched
        (deduplicated repeats included, as in ``dispatched``), of its
        launch time minus its submit time, in clock units (seconds under a
        wall clock, ticks under the logical one).  Its increase over the
        increase of :attr:`dispatched` across any window is that window's
        mean queue wait.

        ``meta_readbacks`` (per table, and summed over tables) counts the
        appends and evicts whose eviction step read the table's meta back:
        every one on a ``ttl`` table that holds rows, those at capacity on
        an ``lru`` table, none on a ``reject`` table.
        """
        with self._lock:
            if name is not None:
                t = self._state(name)
                return {
                    "rows": t.n, "capacity": t.capacity, "policy": t.policy,
                    "ttl": t.ttl, "backend": t.backend, "version": t.version,
                    "appends": t.appends, "evicted": t.evicted,
                    "meta_readbacks": t.meta_readbacks,
                    "hits": t.hits, "misses": t.misses,
                    "lookups": t.hits + t.misses,
                    "queued": t.queued,
                    "admission": t.admission,
                    "qps_budget": t.qps_budget, "max_queue": t.max_queue,
                    "rejected": t.rejected, "shed": t.shed,
                    "blocked": t.blocked,
                    "index": None if t.index_spec is None else {
                        "sets": t.index_spec.sets,
                        "probes": t.index_spec.probes,
                        "built": t.index is not None,
                        "builds": t.index_builds,
                        "lookups": t.index_lookups,
                        "candidate_fraction":
                            t.index_frac_sum / max(1, t.index_groups),
                    },
                }
            cache_size = getattr(self._dispatch, "_cache_size", None)
            drv = self._driver
            return {
                "tables": {n: self.stats(n) for n in self._tables},
                "pending": len(self._pending),
                "queue_depth": len(self._pending),
                "in_flight": len(self._in_flight),
                "flushes": self.flushes,
                "readbacks": self.readbacks,
                "dedup_hits": self.dedup_hits,
                "dedup_rate": self.dedup_hits / max(1, self.dispatched),
                "fused_fallbacks": self.fused_fallbacks,
                "compilations": int(cache_size()) if cache_size else -1,
                "sharded": self._mesh is not None,
                "merge": self._merge,
                "driver": drv.state if drv is not None else None,
                "admission": {
                    "rejected": sum(t.rejected for t in
                                    self._tables.values()),
                    "shed": sum(t.shed for t in self._tables.values()),
                    "blocked": sum(t.blocked for t in
                                   self._tables.values()),
                },
                "index": {
                    "tables": sum(1 for t in self._tables.values()
                                  if t.index_spec is not None),
                    "built": sum(1 for t in self._tables.values()
                                 if t.index is not None),
                    "builds": sum(t.index_builds
                                  for t in self._tables.values()),
                    "lookups": sum(t.index_lookups
                                   for t in self._tables.values()),
                    "candidate_fraction":
                        sum(t.index_frac_sum for t in self._tables.values())
                        / max(1, sum(t.index_groups
                                     for t in self._tables.values())),
                },
                "queue_wait_s": self.queue_wait_s,
                "meta_readbacks": sum(t.meta_readbacks
                                      for t in self._tables.values()),
            }


# ---------------------------------------------------------------------------
# The pipelined dispatch driver
# ---------------------------------------------------------------------------

class AMDriver:
    """Pipelined dispatch driver for one :class:`AMService`.

    Owns the flush deadline and overlaps the pipeline's three stages —
    host batching (submits keep queueing), device compute (up to
    ``max_in_flight`` dispatched groups), and readback (the completion
    stage, one ``jax.device_get`` per group, retired strictly in dispatch
    order).  Two ways to run it:

    * **Deterministic**: construct directly and step :meth:`run_once`
      (optionally with an explicit ``now=``) — no thread, no wall clock,
      exact control over when dispatch and completion happen.  This is how
      the driver tests prove the async path bitwise-identical to
      :meth:`AMService.flush`.
    * **Background**: :meth:`AMService.start_driver` spawns a daemon thread
      running :meth:`run_once` in a loop, woken by submits and a
      ``poll_interval`` heartbeat.  Requires a real clock when the service
      has a ``flush_after`` deadline (the logical clock never advances
      between submits).

    States (see :data:`DRIVER_STATES`): ``idle`` (constructed, stepped by
    hand), ``running`` (thread live), ``draining`` (stop requested, work
    retiring), ``stopped`` (thread joined; the service's sync path remains
    fully usable).
    """

    def __init__(self, service: AMService, *, max_in_flight: int = 2,
                 poll_interval: float = 1e-3):
        if max_in_flight < 1:
            raise ValueError(
                f"max_in_flight must be >= 1, got {max_in_flight}")
        self._service = service
        self.max_in_flight = max_in_flight
        self.poll_interval = poll_interval
        self._wake = threading.Event()
        self._stop_evt = threading.Event()
        self._thread: threading.Thread | None = None
        self.state = "idle"
        self.exception: BaseException | None = None

    def run_once(self, *, now: float | None = None,
                 force: bool = False) -> dict[str, int]:
        """One driver step: dispatch due work, then retire finished groups.

        Dispatches the queue when it is due (``max_batch`` reached, the
        ``flush_after`` deadline expired, a drain was requested, or
        ``force``).  Then retires in-flight groups FIFO: every group whose
        arrays have landed, plus — blocking — any beyond ``max_in_flight``
        (backpressure) or everything when forcing/draining.  Returns
        ``{"launched": lookups dispatched, "completed": groups retired}``.
        """
        svc = self._service
        launched = 0
        with svc._lock:
            force = force or svc._drain_req
            t_now = svc._now() if now is None else float(now)
            if svc._pending and (force
                                 or len(svc._pending) >= svc.max_batch
                                 or svc._deadline_due(t_now)):
                launched = svc._launch_pending(t_now)
        completed = 0
        while True:
            with svc._lock:
                over = (force or svc._drain_req
                        or len(svc._in_flight) > self.max_in_flight)
            if not svc._complete_next(only_ready=not over):
                break
            completed += 1
        return {"launched": launched, "completed": completed}

    # -- thread lifecycle ----------------------------------------------------

    def is_alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "AMDriver":
        if self.is_alive():
            raise RuntimeError("driver already running")
        self._stop_evt.clear()
        self.exception = None
        self.state = "running"
        self._thread = threading.Thread(target=self._loop, name="am-driver",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, *, drain: bool = True, timeout: float = 10.0) -> None:
        """Stop the background thread; with ``drain`` retire all work first."""
        if self._thread is not None and self._thread.is_alive():
            if drain:
                self.state = "draining"
                self._service.drain(timeout)
            self._stop_evt.set()
            self._wake.set()
            self._thread.join(timeout)
        self.state = "stopped"

    def __enter__(self) -> "AMDriver":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def _loop(self) -> None:
        try:
            while not self._stop_evt.is_set():
                r = self.run_once()
                if not r["launched"] and not r["completed"]:
                    with TraceAnnotation("am.driver.wait"):
                        self._wake.wait(self.poll_interval)
                    self._wake.clear()
        except BaseException as e:               # pragma: no cover - safety
            self.exception = e
            self.state = "stopped"
            with self._service._cv:
                self._service._cv.notify_all()
