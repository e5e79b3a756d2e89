"""The load generator: one general reader of traffic files.

A traffic file names the loop and its parameters:

* ``"loop": "open"`` — lookups sent on a schedule at ``rate_per_s``
  whatever the service does.  Every seed gets the same set of gaps between
  arrivals (the quantiles of an exponential distribution at that rate,
  scaled to fill the window) in its own order, so runs differ in order and
  not in load.  Each lookup is timed from when it was due.
* ``"loop": "closed"`` — ``clients`` clients, each issuing its next
  operation when the last one completes (YCSB's ``threadcount``); one
  thread keeps them all busy.

``ops`` gives the share of each operation (``read``, ``insert``) in blocks
of ``BLOCK`` operations, so every block holds the same mix in its own
order.  ``read_dist`` picks what a read asks for:

* ``uniform`` — a query of the table's query set, uniformly;
* ``scrambled_zipfian`` — YCSB workloads a-c: a loaded record's key, Zipfian
  popularity scattered over the key space;
* ``latest`` — YCSB workload d: the newest acknowledged records most often.

An insert appends the next record of the configuration's pool.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

from onchip import ycsb

#: Operations per block of the mix.
BLOCK = 100
#: How long the generator sleeps between looks at the outstanding lookups.
POLL_S = 2e-4
#: How long after the window an answer may still come.
GRACE_S = 60.0

READ, INSERT = 0, 1


@dataclasses.dataclass
class Log:
    """One record per operation issued, in issue order."""

    kind: list = dataclasses.field(default_factory=list)
    key: list = dataclasses.field(default_factory=list)
    rows: list = dataclasses.field(default_factory=list)   # rows acked at issue
    due: list = dataclasses.field(default_factory=list)    # s after window open
    sent: list = dataclasses.field(default_factory=list)
    done: list = dataclasses.field(default_factory=list)   # nan: never
    fut: list = dataclasses.field(default_factory=list)
    resp: list = dataclasses.field(default_factory=list)
    error: list = dataclasses.field(default_factory=list)
    insert_ms: list = dataclasses.field(default_factory=list)

    def add(self, kind, key, rows, due, sent) -> int:
        self.kind.append(kind)
        self.key.append(key)
        self.rows.append(rows)
        self.due.append(due)
        self.sent.append(sent)
        self.done.append(np.nan)
        self.fut.append(None)
        self.resp.append(None)
        self.error.append(None)
        return len(self.kind) - 1


class Client:
    """The calls a user makes, each inside a host span of the benchmark."""

    def __init__(self, svc, table: str, cfg: dict, traffic: dict, data,
                 rows: int):
        self.svc, self.table, self.k = svc, table, traffic["k"]
        self.data = data
        self.keyed = cfg["kind"] == "keyed"
        self.rows = rows                     # acknowledged rows

    def query(self, key: int) -> np.ndarray:
        src = self.data.pool if self.keyed else self.data.queries
        return src[key].astype(np.int32)

    def read(self, key: int):
        with TraceAnnotation("bench.submit"):
            return self.svc.submit(self.table, self.query(key), k=self.k)

    def result(self, fut):
        with TraceAnnotation("bench.result"):
            return fut.result(timeout=GRACE_S)

    def insert(self, log: Log) -> None:
        rec = self.rows
        t0 = time.perf_counter()
        with TraceAnnotation("bench.append"):
            self.svc.append(self.table, self.data.pool[rec:rec + 1],
                            self.data.values(rec, rec + 1))
        log.insert_ms.append((time.perf_counter() - t0) * 1e3)
        self.rows += 1


def mix(ops: dict, rng, n: int) -> np.ndarray:
    """``n`` operation kinds, each block of ``BLOCK`` holding the mix."""
    block = np.concatenate([
        np.full(round(share * BLOCK), {"read": READ, "insert": INSERT}[op],
                np.int8) for op, share in sorted(ops.items())])
    if len(block) != BLOCK:
        raise ValueError(f"ops shares {ops} are not whole per {BLOCK}")
    blocks = -(-n // BLOCK)
    return np.concatenate([rng.permutation(block)
                           for _ in range(blocks)])[:n]


class Keys:
    """What each read asks for, by ``read_dist``."""

    def __init__(self, traffic: dict, cfg: dict, rng, n: int):
        self.dist = traffic["read_dist"]
        self.u = rng.random(n)
        if self.dist == "uniform":
            self.fixed = (self.u * cfg["query_set"]).astype(np.int64)
        elif self.dist == "scrambled_zipfian":
            self.fixed = ycsb.scrambled_zipfian(self.u, cfg["recordcount"])
        elif self.dist == "latest":
            self.fixed = None
            self.zipf = ycsb.Zipfian(cfg["recordcount"])
        else:
            raise ValueError(f"unknown read_dist {self.dist!r}")

    def __call__(self, j: int, rows: int) -> int:
        if self.fixed is not None:
            return int(self.fixed[j])
        return ycsb.latest(self.u[j], self.zipf, rows)


def arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times in [0, seconds): one fixed set of gaps, in the seed's order."""
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def _collect(client: Client, log: Log, out: collections.deque, t0: float,
             on_done=None) -> int:
    """Resolve the answered lookups at the head of ``out`` (answers come
    back in the order lookups were sent); returns how many."""
    got = 0
    while out and log.fut[out[0]].done:
        j = out.popleft()
        log.resp[j] = client.result(log.fut[j])
        log.done[j] = time.perf_counter() - t0
        log.fut[j] = None
        got += 1
        if on_done is not None:
            on_done(j)
    return got


def open_loop(client: Client, traffic: dict, cfg: dict, seconds: float,
              rng, window) -> Log:
    due = arrivals(traffic["rate_per_s"], seconds, rng)
    n = len(due)
    kinds = mix(traffic["ops"], rng, n)
    keys = Keys(traffic, cfg, rng, n)
    log, out = Log(), collections.deque()
    window.open()
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            window.close()
        while i < n and due[i] <= now:
            _issue(client, log, out, kinds[i], keys, i, due[i], t0)
            i += 1
            now = time.perf_counter() - t0
        _collect(client, log, out, t0)
        if i >= n and (not out or now > seconds + GRACE_S):
            break
        nxt = due[i] - now if i < n else POLL_S
        time.sleep(min(max(nxt, 0.0), POLL_S))
    return log


def closed_loop(client: Client, traffic: dict, cfg: dict, seconds: float,
                rng, window, *, chunk: int = 1 << 20) -> Log:
    kinds = mix(traffic["ops"], rng, chunk)
    keys = Keys(traffic, cfg, rng, chunk)
    log, out = Log(), collections.deque()
    owner: dict[int, int] = {}
    window.open()
    t0 = time.perf_counter()
    j = 0

    def issue(c: int) -> None:
        nonlocal j
        while time.perf_counter() - t0 < seconds:
            if j >= chunk:
                raise RuntimeError(f"closed loop ran past {chunk} operations")
            now = time.perf_counter() - t0
            idx = _issue(client, log, out, kinds[j], keys, j, now, t0)
            j += 1
            if log.kind[idx] == READ:
                owner[idx] = c
                return

    for c in range(traffic["clients"]):
        issue(c)
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            window.close()
        got = _collect(client, log, out, t0,
                       on_done=lambda idx: issue(owner.pop(idx)))
        if not out and now >= seconds:
            break
        if now > seconds + GRACE_S:
            break
        if not got:
            time.sleep(POLL_S)
    return log


def _issue(client: Client, log: Log, out, kind, keys: Keys, j: int,
           due: float, t0: float) -> int:
    sent = time.perf_counter() - t0
    if kind == INSERT:
        idx = log.add(INSERT, client.rows, client.rows, due, sent)
        try:
            client.insert(log)
        except Exception as e:  # noqa: BLE001 - a refused insert is a result
            log.error[idx] = e
            return idx
        log.done[idx] = time.perf_counter() - t0
        return idx
    key = keys(j, client.rows)
    idx = log.add(READ, key, client.rows, due, sent)
    try:
        log.fut[idx] = client.read(key)
    except Exception as e:  # noqa: BLE001 - a refused lookup is a result
        log.error[idx] = e
        return idx
    out.append(idx)
    return idx


LOOPS = {"open": open_loop, "closed": closed_loop}
