"""The traffic drivers. A traffic mix is a JSON file of parameters; its
``kind`` picks the driver here:

- ``closed_loop``: one caller, ``batch`` queries per call, the next call
  sent when the last returned (each call ends in
  ``block_until_ready``; its answer's copy to the host starts as the
  call is sent and is collected after the call's time is taken). The
  queries are the configuration's query set in an order drawn from the
  seed, cut into batches and cycled.
- ``open_loop``: single-query requests to ``raft_tpu.serve``'s
  ``MicroBatchServer`` at ``rate_per_s``, arriving as a Poisson process
  on a schedule made from the seed before the window opens. Every seed
  gets the same number of requests, ``rate_per_s * seconds``; the seed
  changes only their spacing and order. A request is timed from when it
  was due, not from when the generator sent it, and how late each send
  ran behind its schedule is kept.

``prepare`` does the set-up (places the queries, warms every shape the
window uses) and returns a session; ``session.run(seconds)`` is the
measured window; ``session.close()`` releases what the session holds.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import time
import traceback
from typing import Any, List

import jax
import numpy as np

#: a request not back this long after the window closed never came
LATE_LIMIT_S = 60.0


@dataclasses.dataclass
class Window:
    t0: float
    t_last: float                  # the last completion
    query_idx: np.ndarray          # [answers] query row of each answer
    dist: np.ndarray               # [answers, k]
    ids: np.ndarray                # [answers, k]
    latency_s: List[float]         # per call, or per request
    attempted: int                 # queries
    failed: int                    # queries
    late_s: List[float] = dataclasses.field(default_factory=list)
    batches: List[int] = dataclasses.field(default_factory=list)

    @property
    def answered(self) -> int:
        return int(self.query_idx.shape[0])


def _order(n_queries: int, seed: int) -> np.ndarray:
    return np.random.default_rng([seed, 0x0DE5]).permutation(n_queries)


class ClosedLoop:
    def __init__(self, search, queries, traffic, seed: int):
        batch = traffic["batch"]
        order = _order(queries.shape[0], seed)
        n_batches = max(1, len(order) // batch)
        order = np.resize(order, n_batches * batch).reshape(n_batches, batch)
        host = np.asarray(queries)
        self.search = search
        self.batch_rows = list(order)
        t = time.perf_counter()
        # one transfer of every batch, not one per batch
        self.batches = jax.block_until_ready(
            jax.device_put([host[rows] for rows in order]))
        t_warm = time.perf_counter()
        for _ in range(2):  # the first call compiles
            self._call(self.batches[0])
        print(f"setup queries placed in {t_warm - t:.3f} s, warm-up calls "
              f"{time.perf_counter() - t_warm:.3f} s", file=sys.stderr)

    def _call(self, batch):
        """One call, fenced, with its answer copied to the host as soon
        as the device has it, so that no answer stays on the device."""
        out = self.search(batch)
        for x in out:
            x.copy_to_host_async()
        return jax.block_until_ready(out)

    def run(self, seconds: float) -> Window:
        outs, lat, used = [], [], []
        attempted = failed = 0
        t0 = time.perf_counter()
        t_last, end, i = t0, t0 + seconds, 0
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.perf_counter() < end:
                b = i % len(self.batches)
                i += 1
                attempted += len(self.batch_rows[b])
                ts = time.perf_counter()
                try:
                    with jax.profiler.TraceAnnotation("bench.call"):
                        out = self._call(self.batches[b])
                except Exception:  # noqa: BLE001 — counted, run goes on
                    traceback.print_exc(file=sys.stderr)
                    failed += len(self.batch_rows[b])
                    continue
                t_last = time.perf_counter()
                lat.append(t_last - ts)
                outs.append(tuple(np.asarray(x) for x in out))
                used.append(b)
        dist = [d for d, _ in outs]
        ids = [x for _, x in outs]
        k = outs[0][1].shape[1] if outs else 0
        return Window(
            t0=t0, t_last=t_last,
            query_idx=np.concatenate([self.batch_rows[b] for b in used])
            if used else np.zeros(0, np.int64),
            dist=np.concatenate(dist) if dist else np.zeros((0, k)),
            ids=np.concatenate(ids) if ids else np.zeros((0, k), np.int32),
            latency_s=lat, attempted=attempted, failed=failed, batches=used)

    def close(self) -> None:
        self.batches = []


class OpenLoop:
    def __init__(self, server, tenant: str, queries, traffic, seed: int):
        self.server = server
        self.tenant = tenant
        self.rate = float(traffic["rate_per_s"])
        self.seed = seed
        self.rows = list(np.asarray(queries))
        self.order = _order(len(self.rows), seed)
        # the submit path, the batcher and every bucket on a settle burst
        burst = [server.submit(tenant, self.rows[i], slo_s=None)
                 for i in range(2 * server.config.max_batch)]
        for fut in burst:
            fut.result(timeout=LATE_LIMIT_S)

    def schedule(self, seconds: float):
        """Due times (seconds from the window's start) and query rows."""
        rng = np.random.default_rng([self.seed, 0xA221])
        n = max(1, int(round(self.rate * seconds)))
        gaps = rng.exponential(size=n + 1)
        due = np.cumsum(gaps)[:n] / gaps.sum() * seconds
        return due, np.resize(self.order, n)

    def run(self, seconds: float) -> Window:
        due, qidx = self.schedule(seconds)
        n = len(due)
        done = [0.0] * n
        futs: List[Any] = [None] * n
        late = np.zeros(n)

        def mark(i, _fut):
            done[i] = time.perf_counter()

        failed = 0
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(n):
                t_due = t0 + due[i]
                wait = t_due - time.perf_counter()
                if wait > 0:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(wait)
                late[i] = time.perf_counter() - t_due
                try:
                    fut = self.server.submit(self.tenant,
                                             self.rows[qidx[i]], slo_s=None)
                except Exception:  # noqa: BLE001 — a refused request
                    failed += 1
                    continue
                fut.add_done_callback(functools.partial(mark, i))
                futs[i] = fut
            results = {}
            for i, fut in enumerate(futs):
                if fut is None:
                    continue
                left = t0 + seconds + LATE_LIMIT_S - time.perf_counter()
                try:
                    results[i] = fut.result(timeout=max(left, 0.0))
                except Exception:  # noqa: BLE001 — never came
                    failed += 1
        got = sorted(results)
        lat = [done[i] - (t0 + due[i]) for i in got]
        k = len(results[got[0]][1]) if got else 0
        return Window(
            t0=t0, t_last=max((done[i] for i in got), default=t0),
            query_idx=qidx[got],
            dist=np.stack([results[i][0] for i in got]) if got
            else np.zeros((0, k)),
            ids=np.stack([results[i][1] for i in got]) if got
            else np.zeros((0, k), np.int32),
            latency_s=lat, attempted=n, failed=failed, late_s=list(late))

    def close(self) -> None:
        self.server.stop()


def prepare(traffic, cfg, system, index, base, queries, seed: int):
    """Set up the traffic's driver over a built index; warms its shapes."""
    kind = traffic["kind"]
    if kind == "closed_loop":
        return ClosedLoop(system.searcher(cfg, index, base), queries,
                          traffic, seed)
    if kind == "open_loop":
        from raft_tpu import serve

        registry = serve.IndexRegistry()
        tenant = system.admit(cfg, registry, index, base)
        server = serve.MicroBatchServer(registry, serve.ServerConfig(
            max_batch=traffic["max_batch"], linger_s=traffic["linger_s"],
            queue_depth=traffic["queue_depth"], default_slo_s=None))
        server.start()
        try:
            return OpenLoop(server, tenant, queries, traffic, seed)
        except BaseException:
            server.stop()
            raise
    raise ValueError(f"unknown traffic kind {kind!r}")
