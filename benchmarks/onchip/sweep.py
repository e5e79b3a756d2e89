#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest rate its service sustains.

  python benchmarks/onchip/sweep.py --workload <cell> --seed <n> \
      [--seconds 10] [--rates 50,100,...]

Sets the cell up once (as ``run.py`` does), times one dispatch of each
power-of-two bucket, then offers the cell's traffic at each rate for
``--seconds`` and prints, per rate, the lookups completed per second, the
latency quantiles, lookups per dispatch and whether the backlog grew (the
median latency of the last third of the window against the first).  With no
``--rates`` the rates are fractions of the largest bucket's capacity
(``max_batch`` lookups per dispatch time).  Needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0] = os.path.dirname(HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)

FRACTIONS = (0.1, 0.25, 0.4, 0.55, 0.7, 0.85, 1.0, 1.2)


def bucket_times(svc, client, cfg) -> dict:
    """Seconds from the first submit to the last answer of one synchronous
    group per bucket (median of three), the idle deadline off so that each
    bucket goes out as one group."""
    import statistics
    out, b, key = {}, 1, 0
    saved = svc.flush_after, svc.max_batch
    svc.flush_after = None
    svc.max_batch = cfg["max_batch"] + 1
    while b <= cfg["max_batch"]:
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            futs = [client.read(key + i) for i in range(b)]
            key += b
            svc.flush()
            for f in futs:
                f.result()
            ts.append(time.perf_counter() - t0)
        out[b] = statistics.median(ts)
        b *= 2
    svc.flush_after, svc.max_batch = saved
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from onchip import catalog, datagen, harness, loadgen
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return harness.NO_CHIP
    cell = catalog.find(args.workload)
    cfg, traffic = cell.cfg, cell.traffic
    data = datagen.build(cfg, args.seed)
    svc = harness.make_service(cfg)
    rows = harness.load(svc, cfg, data)
    client = loadgen.Client(svc, harness.TABLE, cfg, traffic, data, rows)
    harness.warm(svc, client, cfg, traffic)
    times = bucket_times(svc, client, cfg)
    print(json.dumps({"workload": args.workload,
                      "setup_s": time.perf_counter() - T_START,
                      "bucket_s": times}), flush=True)
    cap = cfg["max_batch"] / times[cfg["max_batch"]]
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [round(f * cap, 1) for f in FRACTIONS])
    svc.start_driver(max_in_flight=cfg["max_in_flight"])
    for i, rate in enumerate(rates):
        before = harness.counters(svc)
        tr = dict(traffic, rate_per_s=rate)
        log = loadgen.open_loop(client, tr, cfg, args.seconds,
                                np.random.default_rng([args.seed, 10 + i]),
                                harness.Window())
        after = harness.counters(svc)
        due, done = np.asarray(log.due), np.asarray(log.done)
        lat = (done - due) * 1e3
        ok = ~np.isnan(lat)
        third = len(lat) // 3
        groups = max(1, after["flushes"] - before["flushes"])
        print(json.dumps({
            "rate_per_s": rate, "offered": len(lat),
            "completed_per_s": float(np.sum(done <= args.seconds)
                                     / args.seconds),
            "p50_ms": float(np.percentile(lat[ok], 50)),
            "p95_ms": float(np.percentile(lat[ok], 95)),
            "p99_ms": float(np.percentile(lat[ok], 99)),
            "first_third_p50_ms": float(np.nanmedian(lat[:third])),
            "last_third_p50_ms": float(np.nanmedian(lat[-third:])),
            "lookups_per_dispatch": (after["dispatched"]
                                     - before["dispatched"]) / groups,
            "groups": groups,
            "compiles": after["compilations"] - before["compilations"],
            "unanswered": int(np.sum(~ok))}), flush=True)
    svc.stop_driver()
    return 0


if __name__ == "__main__":
    sys.exit(main())
