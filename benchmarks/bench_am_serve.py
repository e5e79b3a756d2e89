"""AMService under a Zipfian lookup workload: hit-rate + latency vs capacity.

The serving claim behind the paper's headline numbers is that an associative
cache in front of a model absorbs skewed traffic.  This benchmark streams a
Zipf(s)-distributed key workload through a capacity-bounded LRU table
(misses are appended, like a response cache) and reports, per capacity:

  * hit-rate once the cache is warm;
  * p50 / p99 single-lookup latency (submit + flush + readback, the full
    service path — NOT a bare ``am.search`` call);
  * micro-batched throughput (``--batch`` lookups coalesced per flush) and
    the cross-request dedup rate inside those batches — Zipfian traffic
    repeats keys within a wave, so the service dispatches far fewer rows
    than it serves (the win scales with skew ``s`` and batch size).

``--saturation`` runs the pipelined-driver sweep instead: offered-load
waves through the synchronous flush path vs the background
:class:`AMDriver` (dispatch overlapped with readback), reporting
throughput, p50/p99 queue wait, the estimated device-compute fraction a
pipeline can hide, throughput scaling with concurrent tables, and the
admission-control shed counters under deliberate oversubmission.

``--snapshot`` runs the durability sweep instead: snapshot/restore wall
time and bytes-on-disk vs table size, plus the recovery-path numbers the
chaos harness bounds — time from ``restore()`` to the first resolved
lookup, on the same and on a different bank count (elastic reshard).

  PYTHONPATH=src:. python benchmarks/bench_am_serve.py
  PYTHONPATH=src:. python benchmarks/bench_am_serve.py --smoke    # CI guard
  PYTHONPATH=src:. python benchmarks/bench_am_serve.py --smoke --saturation
  PYTHONPATH=src:. python benchmarks/bench_am_serve.py --smoke --snapshot
"""

from __future__ import annotations

import argparse
import pathlib
import tempfile
import time

import numpy as np

from benchmarks.common import emit
from repro.serve.am_service import AMService, _next_pow2


def zipf_probs(population: int, s: float) -> np.ndarray:
    ranks = np.arange(1, population + 1, dtype=np.float64)
    p = ranks ** -s
    return p / p.sum()


def run(smoke: bool = False, *, capacities=None, population: int = 2048,
        requests: int = 20_000, dim: int = 64, zipf_s: float = 1.1,
        batch: int = 64, backend: str = "ref", policy: str = "lru",
        ttl: float | None = None) -> None:
    if smoke:
        capacities = capacities or (16, 32)
        population, requests, batch = 128, 400, 16
    else:
        capacities = capacities or (64, 256, 1024)
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 8, (population, dim)).astype(np.int32)
    probs = zipf_probs(population, zipf_s)
    workload = rng.choice(population, size=requests, p=probs)

    for capacity in capacities:
        svc = AMService(max_batch=batch)
        svc.create_table("kv", width=dim, bits=3, capacity=capacity,
                         policy=policy, ttl=ttl, backend=backend)
        warm = requests // 4           # hit-rate measured after warmup only
        hits = 0
        lat_us: list[float] = []
        for step, pid in enumerate(workload):
            t0 = time.perf_counter()
            resp = svc.lookup("kv", codes[pid])
            lat_us.append(1e6 * (time.perf_counter() - t0))
            if resp.hit:
                hits += step >= warm
            else:
                svc.append("kv", codes[pid], values=[int(pid)])
        hit_rate = hits / max(1, requests - warm)

        # micro-batched regime: `batch` coalesced lookups per flush —
        # duplicate keys inside each wave dispatch once (dedup)
        n_flushes = 20 if not smoke else 4
        for pid in workload[:batch]:   # warm the batch-bucket compile
            svc.submit("kv", codes[pid])
        svc.flush()
        base_dedup = svc.stats()["dedup_hits"]
        t0 = time.perf_counter()
        for i in range(n_flushes):
            futs = [svc.submit("kv", codes[pid])
                    for pid in workload[i * batch:(i + 1) * batch]]
            svc.flush()
            for fut in futs:
                fut.result()
        batched_us = 1e6 * (time.perf_counter() - t0) / (n_flushes * batch)
        dedup_rate = (svc.stats()["dedup_hits"] - base_dedup) \
            / (n_flushes * batch)

        stats = svc.stats()
        tstats = stats["tables"]["kv"]
        assert tstats["rows"] <= capacity, "capacity bound violated"
        p50, p99 = np.percentile(lat_us, [50, 99])
        emit(f"am_serve_cap{capacity}", p50,
             f"hit_rate={hit_rate:.3f};p99_us={p99:.0f};"
             f"batched_us_per_lookup={batched_us:.1f};"
             f"batched_dedup_rate={dedup_rate:.3f};"
             f"evicted={tstats['evicted']};"
             f"compilations={stats['compilations']};"
             f"readbacks={stats['readbacks']}")


def _run_waves(svc, codes, workload, names, batch, waves, *,
               sync: bool) -> float:
    """Offer ``waves`` waves of ``batch`` lookups; return the wall seconds.

    ``sync``: flush inline after every wave (launch + readback serial).
    Otherwise the background driver dispatches and the submitting thread
    only blocks at the end — the next wave's host work (query marshalling,
    dedup, padding) overlaps the previous wave's device compute.
    """
    futs = []
    t0 = time.perf_counter()
    for w in range(waves):
        name = names[w % len(names)]
        for pid in workload[w * batch:(w + 1) * batch]:
            futs.append(svc.submit(name, codes[pid]))
        if sync:
            svc.flush()
    for fut in futs:
        fut.result(timeout=120.0)
    return time.perf_counter() - t0


def _queue_wait(svc, since=None):
    """The service's queue-wait counters ``(seconds, lookups)``; with
    ``since`` (an earlier reading), the mean wait in seconds between the
    two readings."""
    now = (svc.stats()["queue_wait_s"], svc.dispatched)
    if since is None:
        return now
    return (now[0] - since[0]) / max(1, now[1] - since[1])


def run_saturation(smoke: bool = False, *, dim: int = 64,
                   population: int = 256, batch: int = 32,
                   waves: int = 48, backend: str = "ref",
                   table_counts=(1, 2, 4)) -> None:
    """Pipelined driver vs synchronous flush at saturation."""
    if smoke:
        batch, waves, table_counts = 16, 12, (1, 2)
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 8, (population, dim)).astype(np.int32)
    workload = rng.integers(0, population, size=waves * batch)

    def mk(n_tables):
        svc = AMService(max_batch=batch, flush_after=0.05,
                        time_fn=time.monotonic)
        names = [f"t{i}" for i in range(n_tables)]
        for name in names:
            svc.create_table(name, width=dim, bits=3, capacity=population,
                             policy="lru", backend=backend)
            svc.append(name, codes, values=list(range(population)))
        # warm EVERY power-of-two padding bucket the run can produce: the
        # driver coalesces however many waves are pending at wake time, so
        # unlike the wave-aligned sync path its bucket sizes are
        # load-dependent — an unwarmed bucket would hide a ~100ms compile
        # inside the measured region (and serialize it in the driver
        # thread).  max_batch is lifted during warmup so the inline
        # auto-flush cannot split a warm wave below its target bucket.
        svc.max_batch = 1 << 30
        size = 1
        while size <= _next_pow2(min(population, waves * batch)):
            futs = [svc.submit(names[0], codes[i % population])
                    for i in range(size)]
            svc.flush()
            for fut in futs:
                fut.result()
            size *= 2
        svc.max_batch = batch
        return svc, names

    # how much of one flush is device compute (the part a pipeline hides):
    # submit-only host time vs full launch+readback time for one wave
    svc, names = mk(1)
    _run_waves(svc, codes, workload, names, batch, waves, sync=True)
    svc.max_batch = 1 << 30           # keep the probe submits from flushing
    t_host = time.perf_counter()
    futs = [svc.submit(names[0], codes[pid]) for pid in workload[:batch]]
    t_host = time.perf_counter() - t_host
    t_full = time.perf_counter()
    svc.flush()
    t_full = time.perf_counter() - t_full + t_host
    for fut in futs:
        fut.result()
    device_frac = max(0.0, 1.0 - t_host / max(t_full, 1e-9))

    results = {}
    for n_tables in table_counts:
        # synchronous reference: launch + readback serial per wave
        svc, names = mk(n_tables)
        _run_waves(svc, codes, workload, names, batch, waves, sync=True)
        warm = _queue_wait(svc)       # warmup waits stay out of the mean
        sync_s = _run_waves(svc, codes, workload, names, batch, waves,
                            sync=True)
        sync_wait = _queue_wait(svc, since=warm)

        # pipelined: background driver, dispatch overlapped with readback
        svc, names = mk(n_tables)
        _run_waves(svc, codes, workload, names, batch, waves, sync=True)
        warm = _queue_wait(svc)
        svc.start_driver(max_in_flight=4)
        try:
            async_s = _run_waves(svc, codes, workload, names, batch, waves,
                                 sync=False)
            async_wait = _queue_wait(svc, since=warm)
        finally:
            svc.stop_driver()
        n_req = waves * batch
        results[n_tables] = n_req / async_s
        emit(f"am_serve_saturation_t{n_tables}",
             1e6 * async_s / n_req,
             f"sync_us_per_lookup={1e6 * sync_s / n_req:.1f};"
             f"async_over_sync_throughput={sync_s / async_s:.2f};"
             f"sync_wait_us={1e6 * sync_wait:.0f};"
             f"async_wait_us={1e6 * async_wait:.0f};"
             f"device_frac={device_frac:.2f};"
             f"in_flight_cap=4")
        # the pipeline must not cost meaningful throughput even when the
        # host share dominates (tiny CPU "device" work); the win tracks
        # device_frac on real accelerators
        assert async_s < sync_s * 2.5, (
            f"pipelined path pathologically slow: {async_s:.3f}s vs "
            f"sync {sync_s:.3f}s")

    if len(results) > 1:
        counts = sorted(results)
        lo, hi = results[counts[0]], results[counts[-1]]
        emit("am_serve_table_scaling", 0.0,
             f"tables={counts};"
             f"throughput_per_s={[f'{results[c]:.0f}' for c in counts]};"
             f"hi_over_lo={hi / max(lo, 1e-9):.2f}")

    # admission control under deliberate oversubmission: the shed table
    # absorbs the burst without queueing it
    svc, names = mk(1)
    svc.max_batch = 1 << 30           # no inline flush: the queue must fill
    svc.create_table("hot", width=dim, bits=3, capacity=population,
                     policy="lru", backend=backend, max_queue=batch,
                     admission="shed")
    svc.append("hot", codes[:8])
    shed_futs = [svc.submit("hot", codes[pid])
                 for pid in workload[:4 * batch]]
    svc.flush()
    for fut in shed_futs:
        fut.result()
    hot = svc.stats("hot")
    assert hot["shed"] > 0, "oversubmission never tripped admission"
    emit("am_serve_admission", 0.0,
         f"offered={4 * batch};shed={hot['shed']};"
         f"admitted={4 * batch - hot['shed']};max_queue={batch}")


def run_snapshot(smoke: bool = False, *, dim: int = 64,
                 sizes=(1024, 8192), backend: str = "ref") -> None:
    """Durability sweep: snapshot/restore cost + elastic recovery time."""
    import jax
    from jax.sharding import Mesh

    if smoke:
        sizes = (128, 512)
    rng = np.random.default_rng(0)
    devs = jax.devices()
    meshes = {1: None}
    for banks in (2, 4):
        if banks <= len(devs):
            meshes[banks] = Mesh(
                np.array(devs[:banks]).reshape(banks,), ("model",))

    for rows in sizes:
        codes = rng.integers(0, 8, (rows, dim)).astype(np.int32)
        svc = AMService(max_batch=32)
        svc.create_table("kv", width=dim, bits=3, capacity=rows,
                         backend=backend)
        svc.append("kv", codes, values=list(range(rows)))
        query = codes[rng.integers(rows)]
        svc.lookup("kv", query)        # warm the dispatch compile

        with tempfile.TemporaryDirectory() as d:
            t0 = time.perf_counter()
            svc.snapshot(d)
            snap_s = time.perf_counter() - t0
            size_mb = sum(p.stat().st_size
                          for p in pathlib.Path(d).rglob("*")
                          if p.is_file()) / 1e6
            recov = {}
            for banks, mesh in meshes.items():
                t0 = time.perf_counter()
                restored = AMService.restore(d, mesh=mesh)
                resp = restored.lookup("kv", query)
                recov[banks] = time.perf_counter() - t0
                assert resp.hit, "restored table lost the queried row"
        emit(f"am_snapshot_rows{rows}", 1e6 * snap_s,
             f"disk_mb={size_mb:.2f};"
             + ";".join(f"recovery_b{b}_ms={1e3 * s:.0f}"
                        for b, s in sorted(recov.items())))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload + capacities (CI guard)")
    ap.add_argument("--saturation", action="store_true",
                    help="pipelined-driver saturation sweep instead of the "
                         "Zipfian capacity sweep")
    ap.add_argument("--snapshot", action="store_true",
                    help="durability sweep (snapshot/restore cost + elastic "
                         "recovery time) instead of the capacity sweep")
    ap.add_argument("--backend", default="ref")
    ap.add_argument("--batch", type=int, default=64)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.saturation:
        run_saturation(smoke=args.smoke, backend=args.backend)
    elif args.snapshot:
        run_snapshot(smoke=args.smoke, backend=args.backend)
    else:
        run(smoke=args.smoke, backend=args.backend, batch=args.batch)
