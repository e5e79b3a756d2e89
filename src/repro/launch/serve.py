"""Serving driver: continuous-batching engine fronted by the AM cache service.

Requests are drawn from a small prompt pool (so the workload repeats itself,
like real traffic); every prompt is first batch-looked-up in an
:class:`repro.serve.AMService` response table (one micro-batched dispatch for
the whole wave), only the unique misses run through the
:class:`ContinuousBatcher`, and their generations are appended back so later
repeats hit.

The cache service runs on a wall-clock ``flush_after`` deadline owned by a
background :class:`AMDriver` (``svc.start_driver()``) — lookups coalesce
while the deadline lasts and the driver dispatches when it expires, even
when no further submits arrive (the idle-traffic case an in-``submit``-only
check would miss).  Waiting is event-driven: ``fut.result(timeout=...)``
blocks on the driver's completion stage, so there is no busy-wait poll loop
here any more.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --requests 6
  PYTHONPATH=src python -m repro.launch.serve --smoke          # CI smoke
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import ALIASES, get_config
from repro.core import hdc
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import transformer
from repro.serve import AMService, IndexSpec
from repro.serve.engine import Engine
from repro.serve.scheduler import ContinuousBatcher, Request

CACHE_DIM = 128        # hypervector width of the response-cache key
CACHE_BITS = 3


def parse_args(argv=None):
    """Parse the serving driver's CLI flags (``argv=None`` -> ``sys.argv``).

    Split out of :func:`main` so the flag surface is unit-testable without
    booting an engine: ``tests/test_launch_serve.py`` drives this parser and
    :func:`build_cache_service` directly.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--am-cache", type=int, default=8, metavar="CAPACITY",
                    help="AM response-cache capacity (0 disables the cache)")
    ap.add_argument("--am-sharded", action="store_true",
                    help="route the AM cache through am.search_sharded, "
                         "its rows banked over every device of the host")
    ap.add_argument("--am-merge",
                    choices=("auto", "allgather", "tree", "ring"),
                    default="auto",
                    help="cross-bank candidate merge topology for the "
                         "sharded AM cache (see docs/ARCHITECTURE.md)")
    ap.add_argument("--am-index", type=int, default=0, metavar="SETS",
                    help="route cache lookups through the set-associative "
                         "IVF tier with this many sets once the table grows "
                         "past its build threshold (0 = flat scan; see "
                         "docs/ARCHITECTURE.md layer 2.5)")
    ap.add_argument("--am-probes", type=int, default=1, metavar="P",
                    help="sets probed per indexed lookup (only with "
                         "--am-index)")
    ap.add_argument("--am-snapshot-dir", default=None, metavar="DIR",
                    help="durable-cache directory: commit a snapshot of the "
                         "AM cache there on exit (repro.serve.snapshot "
                         "layout; see docs/ARCHITECTURE.md layer 4.5)")
    ap.add_argument("--am-restore", action="store_true",
                    help="warm-restart the AM cache from --am-snapshot-dir "
                         "before serving (elastic: the mesh may have a "
                         "different bank count than the snapshotting run); "
                         "ignored when the directory holds no committed "
                         "snapshot yet")
    return ap.parse_args(argv)


def build_cache_service(args, mesh, *, start_driver=True):
    """Build the AM response-cache service the parsed flags describe.

    Returns ``None`` when ``--am-cache 0`` disabled the cache.  Otherwise:
    a deadline-batched :class:`AMService` — sharded over ``mesh`` iff
    ``--am-sharded``, merge topology from ``--am-merge`` — holding one
    ``"responses"`` table (pallas backend, LRU at ``--am-cache`` rows),
    routed through the IVF tier iff ``--am-index SETS`` with ``--am-probes``
    probes.  ``start_driver=False`` skips the background driver so tests
    can step the service deterministically.

    With ``--am-restore`` and a committed snapshot under
    ``--am-snapshot-dir``, the service warm-restarts from it instead —
    tables, payloads and row counts survive the process boundary, and the
    snapshot's bank layout reshards elastically onto this run's mesh.
    """
    if not args.am_cache:
        return None
    restored = None
    if args.am_restore and args.am_snapshot_dir:
        try:
            restored = AMService.restore(
                args.am_snapshot_dir,
                mesh=mesh if args.am_sharded else None,
                merge=args.am_merge, max_batch=max(64, args.requests),
                flush_after=0.005, time_fn=time.monotonic)
        except FileNotFoundError:
            restored = None          # cold start: nothing committed yet
    if restored is not None:
        if start_driver:
            restored.start_driver()
        return restored
    # deadline-batched: submits queue until the 5 ms flush_after expires;
    # the background driver owns the deadline, so a half-full bucket
    # never waits on another submit arriving.
    svc = AMService(mesh=mesh if args.am_sharded else None,
                    merge=args.am_merge,
                    max_batch=max(64, args.requests),
                    flush_after=0.005, time_fn=time.monotonic)
    spec = (IndexSpec(sets=args.am_index, probes=args.am_probes)
            if args.am_index else None)
    svc.create_table("responses", width=CACHE_DIM, bits=CACHE_BITS,
                     capacity=args.am_cache, policy="lru",
                     backend="pallas", index=spec)
    if start_driver:
        svc.start_driver()
    return svc


def main(argv=None):
    """Serve ``--requests`` prompts; returns the cache service (or None).

    The LM engine runs on one device.  With ``--am-sharded`` the cache
    banks its rows over every device of the host.
    """
    args = parse_args(argv)
    enable_compile_cache()

    cfg = get_config(ALIASES.get(args.arch, args.arch), smoke=args.smoke)
    mesh = make_test_mesh()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    engine = Engine.create(cfg, params, mesh, batch=args.slots,
                           max_len=args.max_len)
    batcher = ContinuousBatcher(engine)

    rng = np.random.default_rng(0)
    pool = [rng.integers(2, cfg.vocab_size,
                         size=rng.integers(3, 9)).astype(np.int32)
            for _ in range(max(2, args.requests // 2))]
    workload = [pool[rng.integers(len(pool))] for _ in range(args.requests)]

    cache_mesh = (jax.make_mesh((jax.device_count(),), ("model",))
                  if args.am_sharded else None)
    svc = build_cache_service(args, cache_mesh)
    if svc is not None:
        proj = hdc.token_key_projection(cfg.vocab_size, CACHE_DIM)
        keys = [np.asarray(hdc.prompt_key(proj, p, CACHE_BITS))
                for p in workload]

    def drain(futs):
        """Event-driven wait on the driver's completion stage (no busy loop)."""
        for f in futs:
            f.result(timeout=60.0)

    t0 = time.time()
    results: dict[int, np.ndarray] = {}
    rep_of: dict[int, int] = {}

    if svc is not None:
        # wave 1: one micro-batched CAM lookup for the whole workload,
        # dispatched by the driver when the deadline expires
        futs = [svc.submit("responses", key) for key in keys]
        drain(futs)
        miss_ids = [i for i, f in enumerate(futs) if not f.result().hit]
        for i, f in enumerate(futs):
            if f.result().hit:
                results[i] = f.result().value
        # only unique missed prompts reach the LM batcher
        unique: dict[bytes, list[int]] = {}
        for i in miss_ids:
            unique.setdefault(keys[i].tobytes(), []).append(i)
        for ids in unique.values():
            for i in ids:
                rep_of[i] = ids[0]
        reps = [ids[0] for ids in unique.values()]
    else:
        reps = list(range(len(workload)))

    for rid in reps:
        batcher.submit(Request(rid=rid, prompt=workload[rid],
                               max_new_tokens=args.max_new))
    done = batcher.run()
    for r in done:
        gen = np.asarray(r.generated, np.int32)
        results[r.rid] = gen
        if svc is not None:
            svc.append("responses", keys[r.rid], values=[gen])

    if svc is not None:
        # wave 2: repeats of missed prompts — again one batch.  A repeat can
        # still miss when the LRU table is smaller than the number of unique
        # prompts generated above; it then falls back to its representative's
        # generation (same prompt, so the same greedy output).
        wave2 = {i: svc.submit("responses", keys[i])
                 for i in range(len(workload)) if i not in results}
        drain(list(wave2.values()))
        for i, fut in wave2.items():
            resp = fut.result()
            results[i] = resp.value if resp.hit else results[rep_of[i]]
        svc.stop_driver()
        if args.am_snapshot_dir:
            step = svc.snapshot(args.am_snapshot_dir)
            print(f"AM cache snapshot committed: step {step} -> "
                  f"{args.am_snapshot_dir}")
    wall = time.time() - t0

    for i, gen in sorted(results.items()):
        src = "GEN" if any(r.rid == i for r in done) else "CAM"
        print(f"req{i}: prompt[{len(workload[i])}] {src} -> "
              f"{[int(x) for x in gen]}")
    print(f"\n{len(results)}/{args.requests} requests, "
          f"{len(done)} generated, {batcher.ticks} engine ticks "
          f"({args.slots} slots), {wall:.1f}s wall")
    if svc is not None:
        s = svc.stats()
        ts = s["tables"]["responses"]
        placement = (f"sharded/{s['merge']}" if s["sharded"] else "local")
        print(f"AM cache [{placement}]: {ts['hits']}/{ts['lookups']} hits, "
              f"{ts['rows']}/{ts['capacity']} rows, "
              f"{s['readbacks']} readbacks, "
              f"{s['compilations']} compilations, "
              f"{s['dedup_hits']} deduped ({s['dedup_rate']:.0%})")
        assert ts["rows"] <= ts["capacity"]
    assert len(results) == args.requests
    return svc


if __name__ == "__main__":
    main()
