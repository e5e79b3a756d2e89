"""One run of one cell: set-up, the measured window, the checks, the metrics.

The program is driven as a user drives it, through its public serving API:
``AMService.create_table(..., backend=...)``, one bulk ``append``,
``start_driver()``, then ``submit``/``result`` and ``append`` from the load
generator.  Set-up (data from the seed, the bulk load, one dispatch of
every power-of-two bucket a group can reach at the cell's ``k``, and a
garbage collection whose survivors are frozen) ends when the window
opens.  With ``trace`` the window runs under the profiler
and the per-layer metrics are read from it; without, the end-to-end ones.

After the window closes and every answer has come (or ``GRACE_S`` passed)
the service is stopped and the answers are compared with the numpy
reference.  Each number compared is printed beside its limit.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import types

import jax
import numpy as np

from onchip import catalog, datagen, loadgen, reference, roofline, ycsb
from onchip import tracereduce

TABLE = "bench"

#: Exit code of a run that finds no chip, or too few.
NO_CHIP = 3


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: catalog.Cell
    seconds: float
    setup_s: float
    log: loadgen.Log
    before: dict                   # service counters as the window opened
    after: dict                    # ... and once every answer had come
    device_kind: str
    trace: tracereduce.Trace | None = None

    @property
    def cfg(self) -> dict:
        return self.cell.cfg

    @property
    def peak(self) -> dict:
        return roofline.peaks(self.device_kind)

    def reads(self) -> list:
        return [j for j, kind in enumerate(self.log.kind)
                if kind == loadgen.READ]

    def read_latency_ms(self) -> np.ndarray:
        """Every read due in the window, timed from when it was due; a
        refused or unanswered read counts as waiting out the grace."""
        j = np.array(self.reads(), np.int64)
        due = np.asarray(self.log.due)[j]
        done = np.asarray(self.log.done)[j]
        never = self.seconds + loadgen.GRACE_S
        return (np.where(np.isnan(done), never, done) - due) * 1e3

    def completed_in_window(self) -> int:
        done = np.asarray(self.log.done, np.float64)
        return int(np.sum(done <= self.seconds))


class Window:
    """The ``bench.window`` host span: opened as the window opens, closed
    when it closes, whatever the loop is still waiting for."""

    def __init__(self):
        self._span = None

    def open(self) -> None:
        self._span = jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN)
        self._span.__enter__()

    def close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


def counters(svc) -> dict:
    out = dict(svc.stats())
    out["dispatched"] = svc.dispatched
    return out


def make_service(cfg: dict):
    from repro.serve import AMService
    svc = AMService(time_fn=time.monotonic, max_batch=cfg["max_batch"],
                    flush_after=cfg["flush_after_ms"] / 1e3)
    svc.create_table(TABLE, width=cfg["width"], bits=cfg["bits"],
                     distance=cfg["distance"], capacity=cfg["capacity"],
                     policy=cfg["policy"], backend=cfg["backend"])
    return svc


def load(svc, cfg: dict, data: datagen.TableData) -> int:
    n = len(data.codes)
    values = data.values(0, n) if cfg["kind"] == "keyed" else None
    svc.append(TABLE, data.codes, values)
    return n


#: The longest host stall an open loop's warm-up covers, in seconds.  The
#: driver launches every pending lookup as one group, so after a stall the
#: backlog goes out as one group of up to ``rate_per_s`` times the stall;
#: 2.2 s is the longest stall measured (PERF.md).
STALL_S = 2.5


def top_bucket(cfg: dict, traffic: dict) -> int:
    """The largest power-of-two bucket the cell's traffic can form: a
    closed loop never has more than ``clients`` lookups pending, an open
    loop can have ``max_batch`` plus a stall's arrivals."""
    if traffic["loop"] == "closed":
        most = traffic["clients"]
    else:
        most = cfg["max_batch"] + traffic["rate_per_s"] * STALL_S
    return 1 << (int(most) - 1).bit_length()


def warm(svc, client: loadgen.Client, cfg: dict, traffic: dict) -> None:
    """One dispatch of every power-of-two bucket up to ``top_bucket`` at
    the cell's ``k``, and one insert where the mix inserts.  The idle
    deadline and the ``max_batch`` trigger are off meanwhile, so each
    bucket's lookups go out as one group."""
    top = top_bucket(cfg, traffic)
    saved = svc.flush_after, svc.max_batch
    svc.flush_after, svc.max_batch = None, top + 1
    try:
        b = 1
        while b <= top:
            futs = [client.read(key) for key in range(b)]
            svc.flush()
            for f in futs:
                f.result()
            b *= 2
    finally:
        svc.flush_after, svc.max_batch = saved
    if traffic["ops"].get("insert"):
        client.insert(loadgen.Log())


def _trace_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, bench_dir: str = catalog.HERE,
        benchmark_json: str = catalog.BENCHMARK_JSON,
        require_chip: bool = True, wrap=None, controls: bool = False,
        keep_trace: str | None = None, out=None,
        err=None) -> dict | None:
    """Run one cell; print its result line; return the result (None when
    there is no chip to run on).  ``keep_trace`` names a directory to keep
    the window's trace in, raw and reduced (for recording test traces)."""
    out = out or sys.stdout
    err = err or sys.stderr
    cell = catalog.find(workload, bench_dir=bench_dir,
                        benchmark_json=benchmark_json)
    if cell.chips != 1:
        raise ValueError(f"{workload}: asks for {cell.chips} chips, and the "
                         f"harness banks no table over chips; such a cell "
                         f"needs a mesh in make_service first")
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"{workload}: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=err)
        return None
    cfg, traffic = cell.cfg, cell.traffic
    # set-up caches every program it compiles; the window keeps JAX's rule
    min_compile = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    data = datagen.build(cfg, seed)
    svc = make_service(cfg)
    if wrap is not None:
        svc = wrap(svc)
    rows = load(svc, cfg, data)
    client = loadgen.Client(svc, TABLE, cfg, traffic, data, rows)
    warm(svc, client, cfg, traffic)
    loaded = client.rows
    # set-up's objects stay out of the window's garbage collections
    gc.collect()
    gc.freeze()
    svc.start_driver(max_in_flight=cfg["max_in_flight"])
    before = counters(svc)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile)
    compiles = _CompileCounter()
    setup_s = time.perf_counter() - t_start

    logdir = tempfile.mkdtemp(prefix="onchip-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(logdir, profiler_options=_trace_options())
    window = Window()
    loop = loadgen.LOOPS[traffic["loop"]]
    log = loop(client, traffic, cfg, seconds,
               np.random.default_rng([seed, 1]), window=window)
    window.close()
    after = counters(svc)
    in_window_compiles = compiles.stop()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    tr = None
    if trace:
        jax.profiler.stop_trace()
        xplane = tracereduce.find_xplane(logdir)
        tr = tracereduce.extract(xplane)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, os.path.join(keep_trace, "trace.xplane.pb"))
            tr.save(os.path.join(keep_trace, "trace.json.gz"))
        shutil.rmtree(logdir, ignore_errors=True)
    svc.stop_driver()
    del svc, client.svc
    gc.unfreeze()
    gc.collect()

    t_ref = time.perf_counter()
    checks, control_readings = verify(cell, data, log, before, after,
                                      seed, seconds, loaded, controls)
    t_ref = time.perf_counter() - t_ref
    ctx = Context(cell=cell, seconds=seconds, setup_s=setup_s, log=log,
                  before=before, after=after,
                  device_kind=devices[0].device_kind, trace=tr)
    metrics = {}
    for m in cell.metrics(trace):
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    attempted = len(log.kind)
    failed = sum(e is not None for e in log.error) + checks["unanswered"][0]
    correct = all(v <= lim for v, lim in checks.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": int(failed), "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tracereduce.busy_s(tr)
        device["window_s"] = tracereduce.window_s(tr)
        result["breakdown"] = {"device_ops": tracereduce.top_ops(tr),
                               "idle_gaps": tracereduce.idle_gaps(tr)}
    if controls:
        result["controls"] = control_readings
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    for line in observations(ctx, in_window_compiles, t_ref):
        print(f"observed: {line}", file=err)
    for name, (v, lim) in checks.items():
        print(f"check {name} = {v} (limit {lim})", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result


def observations(ctx: Context, compiles: int, t_ref: float) -> list:
    """Lines about the run that are no metric: how late the generator ran,
    how the answers came in groups, what compiled, what the check took."""
    log = ctx.log
    reads = ctx.reads()
    lines = [f"compile requests in the window (every program, the "
             f"program's own eager operations included): {compiles}",
             f"reference and controls took {t_ref:.3f} s"]
    if reads:
        late = (np.asarray(log.sent)[reads] - np.asarray(log.due)[reads])
        lines.append(f"generator lag p99 {np.percentile(late, 99) * 1e3:.3f}"
                     f" ms over {len(reads)} reads")
        done = np.asarray(log.done)[reads]
        lat = ctx.read_latency_ms()
        ok = ~np.isnan(done)
        order = np.argsort(done[ok])
        group = np.concatenate([[0], np.cumsum(np.diff(done[ok][order])
                                               > ANSWER_GAP_S)])
        p95 = np.percentile(lat, 95)
        beyond = np.unique(group[lat[ok][order] >= p95]).size
        lines.append(f"answers came in {group[-1] + 1 if len(group) else 0}"
                     f" groups; {beyond} of them hold reads at or beyond "
                     f"the p95 ({p95:.3f} ms)")
    if log.insert_ms:
        lines.append(f"{len(log.insert_ms)} inserts, median "
                     f"{np.median(log.insert_ms):.3f} ms")
    return lines


#: Answers observed further apart than this came from different groups.
ANSWER_GAP_S = 2e-3


class _CompileCounter:
    """Counts JAX's backend compile requests from now until ``stop``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self._live = True

        def listen(event, duration, **_):
            if self._live and event == self.EVENT:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)

    def stop(self) -> int:
        self._live = False
        return self.n


def verify(cell, data, log, before, after, seed, seconds, loaded,
           controls=False):
    """The numbers compared, each ``(value, limit)``, and each control's
    reading where ``controls`` is set."""
    cfg, traffic = cell.cfg, cell.traffic
    keyed = cfg["kind"] == "keyed"
    reads = [j for j, kind in enumerate(log.kind) if kind == loadgen.READ]
    answered = [j for j in reads if log.resp[j] is not None]
    checks = {
        "refused": (sum(e is not None for e in log.error), 0),
        "unanswered": (sum(log.resp[j] is None and log.error[j] is None
                           for j in reads), 0),
        "window_compiles": (after["compilations"] - before["compilations"],
                            0),
        "evicted": (after["tables"][TABLE]["evicted"], 0),
    }
    if keyed:
        checks["wrong_key"] = (_wrong_keys(cfg, data, log, answered), 0)

    rng = np.random.default_rng([seed, 2])
    size = min(traffic["check_sample"], len(answered))
    sample = np.sort(rng.choice(answered, size, replace=False)) \
        if size else np.zeros(0, np.int64)
    src = data.pool if keyed else data.queries
    queries = src[[log.key[j] for j in sample]]
    table = data.pool[:max([log.rows[j] for j in sample], default=0)] \
        if keyed else data.codes
    limit = np.array([log.rows[j] for j in sample])
    args = dict(k=traffic["k"], distance=cfg["distance"], bits=cfg["bits"])
    values = data.records if keyed else None
    want = reference.topk(queries, table, limit=limit, **args)
    wants = [reference.expected(i, d, values) for i, d in zip(*want)]
    checks["mismatched"] = (sum(
        reference.differs(log.resp[j], w) is not None
        for j, w in zip(sample, wants)), 0)

    readings = {}
    if controls:
        for g in traffic["exercises"]:
            idx, dist = reference.CONTROLS[g](queries, table, limit=limit,
                                              loaded=loaded, **args)
            got = [types.SimpleNamespace(**reference.expected(i, d, values))
                   for i, d in zip(idx, dist)]
            readings[g] = sum(reference.differs(a, w) is not None
                              for a, w in zip(got, wants))
    return checks, readings


def _wrong_keys(cfg, data, log, answered) -> int:
    """Keyed reads not answered with their key's lowest row, at distance 0,
    exactly, with that row's record."""
    keys = ycsb.fnvhash64(np.arange(cfg["capacity"]))
    _, first, inv = np.unique(keys, return_index=True, return_inverse=True)
    first_row = first[inv]
    wrong = 0
    for j in answered:
        r = log.resp[j]
        row = int(first_row[log.key[j]])
        ok = (int(r.indices[0]) == row and float(r.distances[0]) == 0.0
              and bool(r.exact[0]) and r.value is not None
              and np.array_equal(np.asarray(r.value), data.records[row]))
        wrong += not ok
    return wrong
