#!/usr/bin/env python3
"""Smoke run of the served associative-search path on a TPU.

Drives :class:`repro.serve.AMService` through the calls a user makes —
``create_table(..., backend="pallas")``, ``append``, ``start_driver()``,
``submit(...).result()`` — at deployment size, and checks every response
bitwise (indices, distances, flags, match counts) against
``am.search(..., backend="ref")`` over the same rows and queries.  Also
checks that each compiled dispatch holds the Pallas kernel
(``tpu_custom_call``), so a kernel that silently interprets fails the run.

  python chip_smoke.py              # one chip: flat, L1, ternary, launcher
  python chip_smoke.py --chips 4    # four chips: the sharded phase only

Phases (one chip):

* flat   — Hamming nearest match, 2^20 rows x 128 cells at 3 bits in an LRU
           table filled to capacity; 512 lookups (half of them stored rows
           drawn Zipfian) at k = 10 and at k = 100, max_batch 64.
* l1     — L1 distance, 2^16 rows x 128 cells (thermometer width 896), k = 10.
* ternary — 2^16 prefix-masked rows, multi-match windows of 8.
* launcher — ``repro.launch.serve.main`` with its response cache.

Sharded (four chips): a 2^21 x 128 table banked over a
``jax.make_mesh((4,), ("model",))`` mesh; for each cross-bank merge
(allgather, tree, ring) 256 lookups at k = 10 and 64 at k = 64.

Data comes from ``--seed``.  Lines before the last are smoke observations
(wall and compile seconds, peak device bytes), not measurements.  The last
line is one JSON object naming the device.  Exits non-zero, without that
line, when JAX finds no TPU or any check fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core import am  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import AMService  # noqa: E402

WIDTH, BITS = 128, 3
MAX_BATCH = 64
REF_CHUNK = 64          # queries per reference search call
MERGES = ("allgather", "tree", "ring")


def observe(msg: str) -> None:
    print(f"[smoke observation] {msg}", flush=True)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def zipf_rows(rng, n_rows: int, count: int) -> np.ndarray:
    """``count`` row ids drawn Zipfian (s = 1.1) over a seeded permutation."""
    rank = np.minimum(rng.zipf(1.1, count), n_rows) - 1
    return rng.permutation(n_rows)[rank]


def flat_queries(rng, codes: np.ndarray, count: int) -> np.ndarray:
    """Half stored rows drawn Zipfian, a quarter of them with a few cells
    changed (near matches), a quarter uniform random words."""
    n = codes.shape[0]
    half, quarter = count // 2, count // 4
    hits = codes[zipf_rows(rng, n, half)]
    near = codes[zipf_rows(rng, n, quarter)].copy()
    for row in near:
        cells = rng.choice(WIDTH, rng.integers(1, 9), replace=False)
        row[cells] = (row[cells] + 1) % (1 << BITS)
    rand = rng.integers(0, 1 << BITS, (count - half - quarter, WIDTH))
    return np.concatenate([hits, near, rand]).astype(np.int32)


def ref_search(table, queries: np.ndarray, **kw) -> dict:
    """``am.search(..., backend="ref")`` in fixed-size query chunks, as a
    dict of host arrays keyed by result field."""
    parts = jax.device_get([
        am.search(table, queries[i:i + REF_CHUNK], backend="ref", **kw)
        for i in range(0, len(queries), REF_CHUNK)])
    return {f.name: np.concatenate([getattr(p, f.name) for p in parts])
            for f in dataclasses.fields(parts[0])}


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def check_responses(label: str, responses, want: dict) -> None:
    """Every response must equal its reference row bitwise."""
    idx = np.where(np.isfinite(want["distances"]), want["indices"], -1)
    for i, r in enumerate(responses):
        got = {"indices": r.indices, "distances": r.distances,
               "exact": r.exact, "matched": r.matched}
        exp = {"indices": idx[i], "distances": want["distances"][i],
               "exact": want["exact"][i], "matched": want["matched"][i]}
        if "match_count" in want:
            got.update(match_count=r.match_count, overflow=r.overflow)
            exp.update(match_count=int(want["match_count"][i]),
                       overflow=bool(want["overflow"][i]))
        for f in got:
            if not np.array_equal(_bits(got[f]), _bits(exp[f])):
                raise AssertionError(
                    f"{label}: lookup {i} field {f!r} differs from ref: "
                    f"got {got[f]!r}, want {exp[f]!r}")


def check_kernel(svc, name: str, label: str, **kw) -> float:
    """Compile the dispatch ``name``'s lookups run; assert it holds the
    Pallas kernel.  Returns the compile seconds."""
    t0 = time.perf_counter()
    text = svc.lower(name, batch=MAX_BATCH, **kw).compile().as_text()
    secs = time.perf_counter() - t0
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{label}: compiled dispatch holds no "
                             "tpu_custom_call (the kernel is not compiled)")
    return secs


def serve_and_check(svc, name: str, queries: np.ndarray, label: str, *,
                    want: dict | None = None, **kw) -> list:
    """Submit every query through the running driver, compare with ``want``
    (default: the ref search of the table's live rows), and return the
    responses."""
    compile_s = check_kernel(svc, name, label, **kw)
    t0 = time.perf_counter()
    futs = [svc.submit(name, q, **kw) for q in queries]
    responses = [f.result(timeout=600.0) for f in futs]
    serve_s = time.perf_counter() - t0
    if want is None:
        want = ref_search(svc.live_table(name), queries, **kw)
    check_responses(label, responses, want)
    observe(f"{label}: {len(queries)} lookups equal ref; dispatch compile "
            f"{compile_s:.3f} s, submit-to-last-result {serve_s:.3f} s")
    return responses


def new_service(**kw):
    return AMService(time_fn=time.monotonic, max_batch=MAX_BATCH,
                     flush_after=0.002, **kw)


def phase_nearest(rng, rows: int, lookups: int, *, distance: str,
                  ks: tuple[int, ...]) -> None:
    """Nearest match over a full LRU table of uniform random rows."""
    codes = rng.integers(0, 1 << BITS, (rows, WIDTH), dtype=np.int32)
    queries = flat_queries(rng, codes, lookups)
    svc = new_service()
    svc.create_table(distance, width=WIDTH, bits=BITS, distance=distance,
                     capacity=rows, policy="lru", backend="pallas")
    svc.append(distance, codes)
    svc.start_driver()
    try:
        for k in ks:
            serve_and_check(svc, distance, queries, f"{distance} k={k}", k=k)
    finally:
        svc.stop_driver()


def prefix_table(rng, rows: int, parents: int):
    """Rows share prefixes of a few parent words; each row cares about a
    prefix of 16..128 cells and wildcards the rest (a routing-table shape,
    so one query matches many rows)."""
    base = rng.integers(0, 1 << BITS, (parents, WIDTH), dtype=np.int32)
    parent = rng.integers(0, parents, rows)
    plen = 16 * rng.integers(1, WIDTH // 16 + 1, rows)
    care = (np.arange(WIDTH)[None, :] < plen[:, None]).astype(np.int32)
    tail = rng.integers(0, 1 << BITS, (rows, WIDTH), dtype=np.int32)
    codes = np.where(care == 1, base[parent], tail).astype(np.int32)
    return base, codes, care


def phase_ternary(rng, rows: int, lookups: int) -> None:
    base, codes, care = prefix_table(rng, rows, parents=max(1, rows // 64))
    n_match = 3 * lookups // 4
    keep = 16 * rng.integers(1, WIDTH // 16 + 1, n_match)
    queries = rng.integers(0, 1 << BITS, (lookups, WIDTH), dtype=np.int32)
    src = base[rng.integers(0, len(base), n_match)]
    lead = np.arange(WIDTH)[None, :] < keep[:, None]
    queries[:n_match] = np.where(lead, src, queries[:n_match])
    svc = new_service()
    svc.create_table("tcam", width=WIDTH, bits=BITS, capacity=rows,
                     policy="lru", backend="pallas", ternary=True)
    svc.append("tcam", codes, care=care)
    svc.start_driver()
    try:
        responses = serve_and_check(svc, "tcam", queries,
                                    "ternary matches=8", matches=8)
    finally:
        svc.stop_driver()
    counts = np.array([r.match_count for r in responses])
    observe(f"ternary: {int((counts > 0).sum())} of {len(counts)} lookups "
            f"matched, {int((counts > 8).sum())} overflowed the window, "
            f"largest match count {int(counts.max())}")


def phase_launcher(rng) -> None:
    from repro.launch import serve as launch_serve
    svc = launch_serve.main(["--smoke", "--requests", "16",
                             "--am-cache", "64"])
    stored = np.asarray(svc.live_table("responses").codes)
    queries = np.concatenate([
        stored, rng.integers(0, 1 << BITS, (4, stored.shape[1]))
    ]).astype(np.int32)
    svc.start_driver()
    try:
        serve_and_check(svc, "responses", queries, "launcher cache",
                        k=1)
    finally:
        svc.stop_driver()


def phase_sharded(rng, rows: int, banks: int,
                  waves=((256, 10), (64, 64))) -> None:
    mesh = jax.make_mesh((banks,), ("model",))
    codes = rng.integers(0, 1 << BITS, (rows, WIDTH), dtype=np.int32)
    waves = [(flat_queries(rng, codes, n), k) for n, k in waves]
    ref_table = am.make_table(jax.device_put(codes, jax.devices()[0]),
                              bits=BITS)
    wants = [ref_search(ref_table, q, k=k) for q, k in waves]
    del ref_table
    for merge in MERGES:
        t0 = time.perf_counter()
        svc = new_service(mesh=mesh, merge=merge)
        svc.create_table("banked", width=WIDTH, bits=BITS, capacity=rows,
                         policy="lru", backend="pallas")
        svc.append("banked", codes)
        # the layout the dispatch receives the code slab in
        compiled = svc.lower("banked", batch=MAX_BATCH, k=10).compile()
        layout = jax.tree.leaves(compiled.input_shardings[0][0])[0]
        if layout.is_fully_replicated:
            raise AssertionError(f"sharded slab is not banked: {layout}")
        observe(f"sharded {merge}: slab layout {layout.spec}")
        svc.start_driver()
        try:
            for (queries, k), want in zip(waves, wants):
                serve_and_check(svc, "banked", queries,
                                f"sharded {merge} k={k}", want=want, k=k)
        finally:
            svc.stop_driver()
        svc.drop_table("banked")
        observe(f"sharded {merge}: wall {time.perf_counter() - t0:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, banked over 4 chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    observe(f"compile cache {enable_compile_cache()}")
    observe(f"device {devices[0].device_kind} x{len(devices)}")
    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(rng, 1 << 21, 4))]
    else:
        phases = [
            ("flat", lambda: phase_nearest(rng, 1 << 20, 512,
                                           distance="hamming", ks=(10, 100))),
            ("l1", lambda: phase_nearest(rng, 1 << 16, 256, distance="l1",
                                         ks=(10,))),
            ("ternary", lambda: phase_ternary(rng, 1 << 16, 256)),
            ("launcher", lambda: phase_launcher(rng)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        observe(f"phase {name} passed: wall {time.perf_counter() - t0:.3f} s"
                f", peak device bytes {peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
