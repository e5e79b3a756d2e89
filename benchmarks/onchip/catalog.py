"""Finds a cell's configuration, traffic and metric readers by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and the
metrics each cell reports.  Beside this file:

* ``configs/<config>.json`` — one deployment;
* ``traffic/<config>.<traffic>.json`` — one mix for it;
* ``metrics/<metric>.py`` — one reader per metric, ``read(ctx)`` returning a
  number, or ``None`` where the run has nothing to read for it.

Adding a cell, a mix or a metric adds files and entries; nothing here names
any of them.  A configuration or mix that holds a key no code reads is
refused, so that no file states a setting the run would not keep.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK_JSON = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                              "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: str

    def metrics(self, trace: bool) -> list:
        return self.per_layer if trace else self.end_to_end

    def reader(self, metric: str):
        return load_reader(self.bench_dir, metric)


#: What a configuration file may hold: the settings the harness reads, and
#: prose (``name``, ``source``, ``guarantees``, ``reduced``, ``assumed``).
CONFIG_KEYS = frozenset({
    "name", "source", "guarantees", "reduced", "assumed",
    "kind", "rows", "width", "bits", "distance", "capacity", "query_set",
    "clusters", "recordcount", "fieldcount", "fieldlength", "policy",
    "backend", "max_batch", "flush_after_ms", "max_in_flight"})
#: What a traffic file may hold: the settings ``loadgen`` and the checks
#: read, and the prose ``why``.
TRAFFIC_KEYS = frozenset({
    "why", "loop", "rate_per_s", "clients", "ops", "read_dist", "k",
    "check_sample", "exercises"})


def _json(path: str, keys: frozenset) -> dict:
    with open(path) as f:
        out = json.load(f)
    unread = sorted(set(out) - keys)
    if unread:
        raise ValueError(f"{path}: no code reads {unread}")
    return out


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find(workload: str, *, bench_dir: str = HERE,
         benchmark_json: str = BENCHMARK_JSON) -> Cell:
    with open(benchmark_json) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg = _json(os.path.join(bench_dir, "configs", w["config"] + ".json"),
                CONFIG_KEYS)
    traffic = _json(os.path.join(bench_dir, "traffic",
                                 f"{w['config']}.{w['traffic']}.json"),
                    TRAFFIC_KEYS)
    return Cell(name=workload, chips=w["chips"], cfg=cfg, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)],
                bench_dir=bench_dir)


def load_reader(bench_dir: str, metric: str):
    """``read`` of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    mod_name = "onchip_metric_" + re.sub(r"\W", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
