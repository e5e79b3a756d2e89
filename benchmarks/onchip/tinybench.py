"""A benchmark directory of tiny cells, for the CPU tests.

``make(root)`` writes ``BENCHMARK.json``, ``configs/``, ``traffic/`` and
``metrics/`` under ``root``: the real configurations and mixes with their
sizes cut so that a run takes seconds on the CPU, and the real metric
readers.  ``run(root, workload, ...)`` runs one of its cells in this process
with the harness's look for a chip skipped.
"""

from __future__ import annotations

import io
import json
import os
import shutil
import time

from onchip import catalog

#: Tiny cell -> (the cell of BENCHMARK.json whose metrics it reports,
#: configuration, mix, configuration sizes, traffic changes).  A mix that no
#: cell of BENCHMARK.json runs (``c_zipf``) still runs here, since the code
#: it drives stays.
CELLS = {
    "tiny_l1.steady": ("sift1m_l1.steady_k10", "sift1m_l1", "steady_k10",
                       dict(rows=700, capacity=1000, query_set=300,
                            clusters=8, max_batch=8),
                       dict(rate_per_s=40, check_sample=16)),
    "tiny_kv.latest": ("ycsb_1m.d_latest", "ycsb_1m", "d_latest",
                       dict(recordcount=600, capacity=1024, max_batch=8),
                       dict(clients=8, check_sample=32)),
    "tiny_kv.zipf": ("sift1m_l1.steady_k10", "ycsb_1m", "c_zipf",
                     dict(recordcount=600, capacity=1024, max_batch=8),
                     dict(rate_per_s=40, check_sample=32)),
}


def _read(path):
    with open(path) as f:
        return json.load(f)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(root: str, bench_dir: str = catalog.HERE,
         benchmark_json: str = catalog.BENCHMARK_JSON) -> str:
    bench = _read(benchmark_json)
    cells = {w["name"]: w for w in bench["workloads"]}
    reports = {}
    workloads = []
    for tiny, (like, config, mix, sizes, changes) in CELLS.items():
        cfg = _read(os.path.join(bench_dir, "configs", config + ".json"))
        tiny_config, tiny_mix = tiny.split(".")
        cfg.update(sizes, name=tiny_config)
        _write(os.path.join(root, "configs", tiny_config + ".json"), cfg)
        tr = _read(os.path.join(bench_dir, "traffic", f"{config}.{mix}.json"))
        tr.update(changes)
        _write(os.path.join(root, "traffic", tiny + ".json"), tr)
        workloads.append(dict(cells[like], name=tiny, config=tiny_config,
                              traffic=tiny_mix))
        reports.setdefault(like, []).append(tiny)
    bench["workloads"] = workloads
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            if "workloads" in m:
                m["workloads"] = [t for n in m["workloads"]
                                  for t in reports.get(n, [])]
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    shutil.copytree(os.path.join(bench_dir, "metrics"),
                    os.path.join(root, "metrics"), dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def run(root: str, workload: str, *, seed: int = 2**31 + 17,
        seconds: float = 1.5, trace: bool = False, wrap=None,
        controls: bool = False) -> tuple[dict, str]:
    """The result, and what the run printed to standard error."""
    from onchip import harness
    err, out = io.StringIO(), io.StringIO()
    result = harness.run(workload, seed, seconds, trace,
                         t_start=time.perf_counter(), bench_dir=root,
                         benchmark_json=os.path.join(root, "BENCHMARK.json"),
                         require_chip=False, wrap=wrap, controls=controls,
                         out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == result
    return result, err.getvalue()
