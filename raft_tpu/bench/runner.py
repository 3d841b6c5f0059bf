"""ANN benchmark runner — JSON config → build/search sweeps → CSV export.

TPU-native counterpart of the reference's bench harness
(cpp/bench/ann/src/common/benchmark.hpp gbench driver + JSON conf.hpp;
python/raft-ann-bench run/__main__.py orchestration and
data_export/__main__.py QPS/recall CSV).  One process, no subprocesses:
XLA jit-caching plays the role of the reference's per-algo executables.

Config schema (mirrors run/conf/*.json)::

    {
      "dataset": {"name": "...", "n": 10000, "dim": 128, "n_queries": 1000,
                   "metric": "sqeuclidean"},
      "k": 10,
      "batch_size": 10000,
      "index": [
        {"name": "ivf_flat.n1024", "algo": "ivf_flat",
         "build_param": {"n_lists": 1024},
         "search_params": [{"n_probes": 32}, {"n_probes": 64}]},
        ...
      ]
    }

Per-row search_param extras (popped before the algo sees them):
``batch_size``/``n_queries``/``fence_per_call`` (the reference's batch
1/10 latency protocol), ``filter_selectivity`` (ISSUE 12: pre-filter
the search with a seeded bitset at that set-bit fraction; recall is
measured against EXACT filtered groundtruth shared per selectivity),
and ``leg_env`` (env overrides held for the row's measurement +
diagnostics — how a config pins a dispatch tier for an honest
fused-vs-forced-fallback comparison). All of these stay in the
recorded ``search_param`` so benchdiff's join key distinguishes the
legs.
"""

from __future__ import annotations

import contextlib
import csv
import json
import os
import time

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.obs.spans import env_flag as _env_flag

from . import dataset as ds_mod


@dataclass
class BenchResult:
    """One (algo, build_param, search_param) measurement row — the
    reference's gbench JSON record (qps = items_per_second)."""

    algo: str
    index_name: str
    dataset: str
    k: int
    batch_size: int
    build_s: float
    search_s: float
    qps: float
    recall: float
    build_param: Dict[str, Any] = field(default_factory=dict)
    search_param: Dict[str, Any] = field(default_factory=dict)
    # observability extras (RAFT_TPU_BENCH_OBS=1): per-stage span seconds
    # for one diagnostic batch, and the allocator's PROCESS-LIFETIME
    # peak-HBM high-water mark read at capture time — PJRT has no reset,
    # so this includes the build and all earlier rows (None on backends
    # that don't report, e.g. CPU). The spans wrap the same whole-API
    # calls the timed loop dispatched, synced: they attribute time to
    # the APIs, they do not re-measure the row
    stage_breakdown: Optional[Dict[str, float]] = None
    peak_hbm_bytes: Optional[int] = None
    # p50/p99 of the diagnostic batches' end-to-end search latency
    # (bucket-interpolated Histogram.quantile over OBS_REPS synced
    # calls) — an estimate for tail triage, not the timed QPS protocol
    latency_quantiles: Optional[Dict[str, float]] = None
    # True when the row was measured under the fenced LATENCY protocol
    # (reduced-batch legs): qps includes the per-call host round-trip
    fence_per_call: bool = False
    # roofline cost attribution (RAFT_TPU_BENCH_OBS=1, obs.prof): the
    # XLA cost model of the row's whole compiled search program —
    # flops / bytes_accessed / arith_intensity / memory-vs-compute
    # bound vs the device peak table, plus achieved_bw_frac from the
    # diagnostic batches' p50 latency. None when the search closure
    # can't be traced end-to-end (host-gather paths)
    cost: Optional[Dict[str, Any]] = None
    # environment provenance (jax/jaxlib/libtpu versions, device kind
    # and count, mesh shape) — benchdiff refuses cross-environment
    # comparisons instead of reporting phantom regressions
    env: Optional[Dict[str, Any]] = None


# ---------------------------------------------------------------------------
# algorithm wrappers (reference: bench/ann/src/raft/*_wrapper.h)
# ---------------------------------------------------------------------------

def _algo_brute_force(dsx, build_param, metric):
    from ..neighbors import brute_force

    index = brute_force.build(dsx, metric=build_param.get("metric", metric))

    def search(q, k, sp):
        return brute_force.knn(index, q, k)

    return search, index


def _algo_ivf_flat(dsx, build_param, metric):
    from ..neighbors import ivf_flat

    p = ivf_flat.IndexParams(**{"metric": metric, **build_param})
    index = ivf_flat.build(dsx, p)

    def search(q, k, sp):
        # refine_ratio rides the API's own refined path (SearchParams.
        # refine="f32_regen"): oversample the scan — recovering what
        # the approx hardware top-k trades away — then re-rank exactly
        # through neighbors.refine's dispatch tier, residency-routed by
        # ivf_flat._route_refined (device → fused gather-refine kernel
        # on TPU oversampled shapes; memmap base → host gather)
        sp = dict(sp)
        fb = sp.pop("filter_bitset", None)
        ratio = sp.pop("refine_ratio", 1)
        if ratio > 1:
            return ivf_flat.search(
                index, q, k,
                ivf_flat.SearchParams(**sp, refine="f32_regen",
                                      refine_ratio=float(ratio)),
                filter_bitset=fb, dataset=dsx)
        return ivf_flat.search(index, q, k, ivf_flat.SearchParams(**sp),
                               filter_bitset=fb)

    return search, index


def _algo_ivf_pq(dsx, build_param, metric):
    from ..neighbors import ivf_pq, refine

    bp = dict(build_param)
    refine_ratio = bp.pop("refine_ratio", 1)
    chunked = bp.pop("chunked_build", False)
    chunk_rows = bp.pop("chunk_rows", 1 << 18)
    p = ivf_pq.IndexParams(**{"metric": metric, **bp})
    if chunked:  # streaming build: O(chunk) working set (memmap-friendly)
        base = dsx if isinstance(dsx, np.ndarray) else np.asarray(dsx)
        index = ivf_pq.build_chunked(base, p, chunk_rows=chunk_rows)
    else:
        index = ivf_pq.build(dsx, p)

    host_base = dsx if isinstance(dsx, np.ndarray) else None

    def search(q, k, sp):
        sp = dict(sp)
        fb = sp.pop("filter_bitset", None)
        ratio = sp.pop("refine_ratio", refine_ratio)
        if ratio > 1:
            # the oversampled scan already excludes filtered candidates
            # (the fused tiers stream the mask), so i0 is filter-clean
            # entering the re-rank
            d0, i0 = ivf_pq.search(index, q, k * int(ratio),
                                   ivf_pq.SearchParams(**sp),
                                   filter_bitset=fb)
            if host_base is not None:
                # memmapped base: gather only candidate rows on the host —
                # jitted refine would materialize the whole base in HBM
                return refine.refine_gathered(host_base, q, i0, k,
                                              metric=index.metric)
            return refine.refine(dsx, q, i0, k, metric=index.metric)
        return ivf_pq.search(index, q, k, ivf_pq.SearchParams(**sp),
                             filter_bitset=fb)

    return search, index


def _algo_cagra(dsx, build_param, metric):
    from ..neighbors import cagra

    p = cagra.IndexParams(**{"metric": metric, **build_param})
    index = cagra.build(dsx, p)

    def search(q, k, sp):
        return cagra.search(index, q, k, cagra.SearchParams(**sp))

    return search, index


ALGO_REGISTRY: Dict[str, Callable] = {
    "brute_force": _algo_brute_force,
    "ivf_flat": _algo_ivf_flat,
    "ivf_pq": _algo_ivf_pq,
    "cagra": _algo_cagra,
}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

_ENV_STAMP: Optional[Dict[str, Any]] = None


def environment_stamp() -> Dict[str, Any]:
    """Environment provenance for bench rows (cached per process):
    jax/jaxlib/libtpu versions, backend, device kind/count, local
    device count, and the (flat single-process) mesh shape. Two
    records whose stamps differ are measuring different hardware or
    different compilers — ``tools/benchdiff.py`` refuses to compare
    them instead of reporting phantom regressions. Every field
    degrades to None rather than raising (the stamp must never cost a
    row)."""
    global _ENV_STAMP
    if _ENV_STAMP is not None:
        return _ENV_STAMP
    env: Dict[str, Any] = {}
    try:
        env["jax"] = jax.__version__
    except Exception:
        env["jax"] = None
    try:
        import jaxlib

        env["jaxlib"] = jaxlib.__version__
    except Exception:
        env["jaxlib"] = None
    import importlib.metadata as _md

    try:
        env["libtpu"] = _md.version("libtpu")
    except _md.PackageNotFoundError:  # a CPU-only install
        env["libtpu"] = None
    try:
        env["backend"] = jax.default_backend()
        devs = jax.devices()
        env["device_kind"] = getattr(devs[0], "device_kind", None)
        env["device_count"] = len(devs)
        env["local_device_count"] = jax.local_device_count()
        env["process_count"] = getattr(jax, "process_count", lambda: 1)()
        # flat single-process mesh; multichip records stamp their own
        env["mesh_shape"] = [len(devs)]
    except Exception:
        env.setdefault("backend", None)
        env.setdefault("device_kind", None)
        env.setdefault("device_count", None)
    _ENV_STAMP = env
    return env


def _obs_capture(search_fn, queries, k, sp, batch_size, context):
    """RAFT_TPU_BENCH_OBS=1: run a few diagnostic batches under the
    observability layer in sync mode (every search reports whole-API
    spans, synced on their outputs) and return (seconds_by_span,
    peak_hbm_bytes, latency_quantiles, cost_row). Runs AFTER the timed
    measurement so the synced diagnostics never pollute QPS. Stage values are PER-BATCH means over the reps; the
    quantiles (p50/p99, ``Histogram.quantile`` bucket interpolation)
    come from a ``bench.search_latency_s`` histogram of each rep's
    end-to-end synced call. RAFT_TPU_BENCH_OBS_REPS overrides the rep
    count (default 5). With RAFT_TPU_BENCH_OBS_JSONL set, the captured
    registry is appended to that file, one JSON line per series,
    stamped with ``context``."""
    from raft_tpu import obs
    from raft_tpu.obs import spans as _spans

    try:
        reps = max(1, int(os.environ.get("RAFT_TPU_BENCH_OBS_REPS", "5")))
    except ValueError:
        reps = 5
    reg = obs.MetricsRegistry()
    qb = queries[: min(batch_size, queries.shape[0])]
    prev = _spans._state()  # a RAFT_TPU_OBS=1 enable must survive this
    try:
        # warm-up: the diagnostic batch may be a shape the timed loop
        # never ran, whose first call pays trace+compile. Burn it into
        # a throwaway registry; measure the later calls.
        obs.enable(sync=True, registry=obs.MetricsRegistry())
        jax.block_until_ready(search_fn(qb, k, dict(sp)))
        obs.enable(sync=True, registry=reg)
        # denser-than-default buckets: the quantile estimate is bucket-
        # interpolated, and the default decade buckets would clamp a
        # handful of similar reps straight to min/max
        lat = reg.histogram(
            "bench.search_latency_s",
            buckets=[1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2,
                     2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                     10.0, 30.0, 60.0])
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(search_fn(qb, k, dict(sp)))
            lat.observe(time.perf_counter() - t0)
    finally:
        _spans._restore(prev)
    quantiles = {"p50": round(lat.quantile(0.5), 6),
                 "p99": round(lat.quantile(0.99), 6),
                 "samples": lat.count}
    # roofline cost attribution (obs.prof): trace+compile the SAME
    # search closure the timed loop dispatched as one whole program and
    # read XLA's cost model — flops, bytes accessed, arithmetic
    # intensity, memory-vs-compute bound vs the device peak table.
    # elapsed = the diagnostic p50, so achieved_bw_frac compares the
    # row's realized bandwidth against the chip ceiling. analyze_jit
    # returns None (row kept, columns null) when the closure can't
    # trace end-to-end — e.g. host-gather refine paths.
    cost_row = None
    try:
        from raft_tpu.obs import prof as _prof

        cost = _prof.analyze_jit(lambda q: search_fn(q, k, dict(sp)), qb,
                                 elapsed_s=quantiles["p50"])
        if cost is not None:
            _prof.record(cost, registry=reg, program=context)
            cost_row = cost.as_row()
    except Exception as e:  # diagnostics must never cost a row
        print(f"[bench] prof capture failed ({e!r}) — "
              "row kept without cost columns")
    snap = reg.snapshot()
    stages = {name[len("span."):]: round(h["mean"], 6)
              for name, h in snap["histograms"].items()
              if name.startswith("span.")}
    peak = snap["gauges"].get("hbm.peak_bytes")
    jsonl = os.environ.get("RAFT_TPU_BENCH_OBS_JSONL")
    if jsonl:
        reg.dump_jsonl(jsonl, extra={"context": context})
    return stages, (int(peak) if peak else None), quantiles, cost_row


def _xprof_capture(search_fn, queries, k, sp, batch_size, xprof_dir):
    """RAFT_TPU_XPROF_DIR: bracket one measured batch in a programmatic
    profiler capture (``obs.prof.capture`` — the start/stop
    generalization of the old inline ``jax.profiler.trace`` block) for
    offline XProf/Perfetto analysis."""
    from raft_tpu.obs import prof as _prof

    qb = queries[: min(batch_size, queries.shape[0])]
    cap = _prof.capture(xprof_dir).start()
    try:
        out = search_fn(qb, k, dict(sp))
        jax.block_until_ready(out)
    finally:
        cap.stop()
    if cap.error is not None:
        print(f"[bench] xprof capture unavailable ({cap.error!r})")
    else:
        print(f"[bench] xprof capture written under {xprof_dir}")


@contextlib.contextmanager
def _scoped_env(overrides: Optional[Dict[str, Any]]):
    """Apply a leg's env overrides for the duration of its measurement
    (timed loop + diagnostic captures), restoring prior values — unset
    variables are removed again — even when the leg dies."""
    if not overrides:
        yield
        return
    saved = {name: os.environ.get(name) for name in overrides}
    try:
        for name, val in overrides.items():
            os.environ[name] = str(val)
        yield
    finally:
        for name, prev in saved.items():
            if prev is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = prev


def _filter_leg(data: ds_mod.Dataset, selectivity: float, k: int):
    """Deterministic filtered-search leg state (ISSUE 12): a seeded
    keep mask at ``selectivity``, its packed bitset, and EXACT filtered
    groundtruth (brute force over only the kept rows, kept-row ids
    mapped back to global). Cached on the Dataset per selectivity —
    the fused and forced-fallback rows of one sweep share the mask and
    the GT, so their recall columns are comparable."""
    from raft_tpu.core import bitset as _bitset
    from ..neighbors import brute_force

    cache = getattr(data, "_filter_legs", None)
    if cache is None:
        cache = {}
        data._filter_legs = cache
    key = round(float(selectivity), 6)
    if key in cache:
        return cache[key]
    rng = np.random.default_rng(981_000 + int(key * 1_000_000))
    keep = rng.random(data.n) < key
    if keep.sum() < k:  # degenerate tiny-selectivity guard
        keep[rng.permutation(data.n)[:k]] = True
    bits = _bitset.from_mask(jnp.asarray(keep))
    kept_rows = np.where(keep)[0].astype(np.int64)
    base_kept = jnp.asarray(np.ascontiguousarray(data.base[kept_rows],
                                                 dtype=np.float32))
    index = brute_force.build(base_kept, metric=data.metric)
    # impl="sort": guaranteed-exact GT, same contract as the unfiltered
    # groundtruth above
    _, ids = brute_force.knn(index, jnp.asarray(data.queries), k,
                             impl="sort")
    gt = kept_rows[np.clip(np.asarray(ids), 0, len(kept_rows) - 1)]
    gt = np.where(np.asarray(ids) >= 0, gt, -1).astype(np.int64)
    del index, base_kept
    cache[key] = (bits, gt)
    return cache[key]


def _bench_search(search_fn, queries, k, sp, batch_size, iters=5,
                  fence_per_call=False):
    m = queries.shape[0]
    # pre-split batches ONCE: eager slicing inside the timed loop would
    # add a per-op dispatch to every timed call
    batches = [queries[start : start + batch_size]
               for start in range(0, m, batch_size)]
    jax.block_until_ready(batches)
    ids_all = []
    # warmup/compile + correctness capture
    for qb in batches:
        d, i = search_fn(qb, k, sp)
        ids_all.append(np.asarray(jax.device_get(i)))
    ids = np.concatenate(ids_all, axis=0)
    t0 = time.perf_counter()
    if fence_per_call:
        # LATENCY protocol (the reference's batch-1/10 legs): every call
        # is fenced to the host before the next one dispatches, so the
        # reported rate includes the full per-call round-trip — the
        # number a single-request serving loop would see. Pipelining
        # here would report throughput mislabeled as latency.
        for _ in range(iters):
            for qb in batches:
                jax.device_get(search_fn(qb, k, sp)[1])
    else:
        # timed THROUGHPUT protocol: dispatch all iterations, then fetch
        # every result as the sync fence (gbench's stream-pipelined
        # items_per_second measures the same way). Blocking per call
        # instead adds the full per-call host round-trip to every
        # iteration — that is the fenced LATENCY protocol above.
        outs = [search_fn(qb, k, sp)[1]
                for _ in range(iters) for qb in batches]
        jax.device_get(outs)  # FULL results cross to the host, pipelined
    dt = (time.perf_counter() - t0) / iters
    return ids, dt, m / dt


def run_config(config: Dict[str, Any],
               data: Optional[ds_mod.Dataset] = None,
               verbose: bool = True,
               on_row: Optional[Callable[[BenchResult], None]] = None,
               deadline: Optional[float] = None) -> List[BenchResult]:
    """Run one benchmark config; returns a result row per
    (index, search_param) combination.

    ``on_row`` fires after every completed measurement — callers that
    must survive an external timeout (the driver protocol) persist rows
    incrementally instead of waiting for the full sweep (the
    reference's per-algo subprocess isolation serves the same purpose,
    run/__main__.py:48-103). ``deadline`` (time.time() scale) skips
    remaining index builds / search params once passed."""
    k = int(config.get("k", 10))
    batch_size = int(config.get("batch_size", 10_000))

    mmap_mode = False
    if data is None:
        dcfg = config["dataset"]
        if "dir" in dcfg:  # on-disk .fbin/.ibin dataset directory
            mmap_mode = bool(dcfg.get("mmap", False))
            data = ds_mod.load_dataset(
                dcfg["dir"], dcfg["name"],
                metric=dcfg.get("metric", "sqeuclidean"),
                max_rows=int(dcfg.get("max_rows", -1)), mmap=mmap_mode)
        else:
            data = ds_mod.make_synthetic(
                dcfg.get("name", "synthetic"),
                int(dcfg["n"]), int(dcfg["dim"]), int(dcfg["n_queries"]),
                metric=dcfg.get("metric", "sqeuclidean"),
                seed=int(dcfg.get("seed", 0)),
                hard=bool(dcfg.get("hard", False)),
            )
    # memmapped bases stay host-side: chunked builds page them in; only
    # algos that genuinely need the full matrix pull it to device
    dsx = data.base if mmap_mode else jnp.asarray(data.base)
    if data.groundtruth is None:
        ds_mod.compute_groundtruth(
            data, k=max(k, 10),
            device_base=None if mmap_mode else dsx)
    queries = jnp.asarray(data.queries)
    # config errors fail loudly BEFORE any work; a runtime failure ends
    # the run (rows already handed to ``on_row`` stay with the caller)
    for index_cfg in config["index"]:
        if index_cfg["algo"] not in ALGO_REGISTRY:
            raise ValueError(f"unknown algo {index_cfg['algo']!r} "
                             f"(have {sorted(ALGO_REGISTRY)})")
    results: List[BenchResult] = []
    for index_cfg in config["index"]:
        if deadline is not None and time.time() > deadline:
            print(f"[bench] leg budget exhausted — skipping "
                  f"{index_cfg.get('name')} and later indexes")
            break
        _run_one_index(index_cfg, index_cfg["algo"], dsx, data,
                       queries, k, batch_size, results, verbose,
                       on_row=on_row, deadline=deadline)
    return results


def _run_one_index(index_cfg, algo, dsx, data, queries, k, batch_size,
               results, verbose, on_row=None, deadline=None):
    bp = dict(index_cfg.get("build_param", {}))
    t0 = time.perf_counter()
    search_fn, index_obj = ALGO_REGISTRY[algo](dsx, dict(bp), data.metric)
    # block on the *index* arrays, not the input: async dispatch would
    # otherwise let the build overlap the first search timing
    jax.block_until_ready(
        [leaf for leaf in jax.tree_util.tree_leaves(index_obj)
         if hasattr(leaf, "block_until_ready")])
    build_s = time.perf_counter() - t0
    for sp in index_cfg.get("search_params", [{}]):
        if deadline is not None and time.time() > deadline:
            print(f"[bench] leg budget exhausted — skipping remaining "
                  f"search params of {index_cfg.get('name')}")
            break
        # per-search-param batch/query overrides: the reference ANN
        # protocol measures batch 1/10/10000 (raft_ann_benchmarks), so a
        # search_param may carry "batch_size" (and a trimmed "n_queries"
        # — small batches measure latency, they don't need the full
        # query set) while sharing the dataset, groundtruth and built
        # index with the big-batch rows
        sp = dict(sp)
        row_bs = int(sp.pop("batch_size", batch_size))
        row_nq = sp.pop("n_queries", None)
        # reduced-batch legs default to the fenced LATENCY protocol
        # (that is what batch 1/10 measures); override with
        # "fence_per_call": false to pipeline anyway
        fenced = bool(sp.pop("fence_per_call", row_bs < batch_size))
        # filtered-search legs (ISSUE 12): "filter_selectivity": 0.1
        # pre-filters the search with a seeded bitset at that set-bit
        # fraction; recall is measured against EXACT filtered
        # groundtruth shared across the sweep's rows at the same
        # selectivity (fused vs forced-fallback rows stay comparable)
        fsel = sp.pop("filter_selectivity", None)
        leg_fn, gt = search_fn, data.groundtruth
        if fsel is not None:
            fbits, gt = _filter_leg(data, float(fsel), k)

            def leg_fn(q, kk, s, _fb=fbits, _fn=search_fn):
                return _fn(q, kk, {**s, "filter_bitset": _fb})
        # "leg_env": env overrides scoped to this row's measurement —
        # how a config pins a dispatch tier for an honest fused-vs-
        # forced-fallback comparison (e.g. RAFT_TPU_PALLAS_LUTSCAN=
        # "never" reproduces the pre-ISSUE-12 filtered fallback tier).
        # Held through the obs/xprof captures (they must describe the
        # same program the timed loop ran), restored after the row;
        # recorded in search_param (part of the benchdiff join key).
        leg_env = sp.pop("leg_env", None)
        q_leg = queries if row_nq is None else \
            queries[: min(int(row_nq), queries.shape[0])]
        with _scoped_env(leg_env):
            ids, dt, qps = _bench_search(leg_fn, q_leg, k, sp, row_bs,
                                         fence_per_call=fenced)
            rec = ds_mod.recall(ids, gt[: q_leg.shape[0]])
            stages = peak_hbm = latency_q = cost_row = None
            if _env_flag("RAFT_TPU_BENCH_OBS"):
                try:
                    stages, peak_hbm, latency_q, cost_row = \
                        _obs_capture(
                            leg_fn, q_leg, k, sp, row_bs,
                            context=f"{index_cfg.get('name', algo)} {sp}")
                except Exception as e:  # diagnostics never cost a row
                    print(f"[bench] obs capture failed ({e!r}) — "
                          "row kept without stage breakdown")
            xprof_dir = os.environ.get("RAFT_TPU_XPROF_DIR")
            if xprof_dir:
                _xprof_capture(leg_fn, q_leg, k, sp, row_bs, xprof_dir)
        # the recorded search_param keeps filter_selectivity + leg_env —
        # the join key benchdiff matches rows by must distinguish
        # filtered and env-pinned legs
        sp_rec = dict(sp)
        if fsel is not None:
            sp_rec["filter_selectivity"] = float(fsel)
        if leg_env:
            sp_rec["leg_env"] = dict(leg_env)
        row = BenchResult(
            algo=algo, index_name=index_cfg.get("name", algo),
            dataset=data.name, k=k, batch_size=row_bs,
            build_s=build_s, search_s=dt, qps=qps, recall=rec,
            build_param=bp, search_param=sp_rec,
            stage_breakdown=stages,
            peak_hbm_bytes=peak_hbm, latency_quantiles=latency_q,
            fence_per_call=fenced, cost=cost_row,
            env=environment_stamp(),
        )
        results.append(row)
        if on_row is not None:
            on_row(row)
        if verbose:
            bs_note = f" b={row_bs}" if row_bs != batch_size else ""
            print(f"[bench] {row.index_name} {sp}{bs_note}: "
                  f"qps={qps:,.0f} recall={rec:.4f} build={build_s:.1f}s")
            if stages:
                parts = ", ".join(f"{n}={v * 1e3:.1f}ms"
                                  for n, v in sorted(stages.items()))
                hbm = (f"; peak_hbm={peak_hbm / 2**30:.2f}GiB"
                       if peak_hbm else "")
                lat = (f"; p50={latency_q['p50'] * 1e3:.1f}ms "
                       f"p99={latency_q['p99'] * 1e3:.1f}ms"
                       if latency_q else "")
                print(f"[bench]   stages: {parts}{hbm}{lat}")
            if cost_row and cost_row.get("flops") is not None \
                    and cost_row.get("bytes_accessed") is not None:
                bw = cost_row.get("achieved_bw_frac")
                bw_s = f" bw_frac={bw:.3f}" if bw is not None else ""
                print(f"[bench]   roofline: flops={cost_row['flops']:.3g} "
                      f"bytes={cost_row['bytes_accessed']:.3g} "
                      f"bound={cost_row['bound']}{bw_s}")


def run_config_file(path: str, **kw) -> List[BenchResult]:
    with open(path) as f:
        return run_config(json.load(f), **kw)


def export_csv(results: List[BenchResult], path: str) -> None:
    """QPS/recall CSV (reference: data_export/__main__.py:54-55)."""
    cols = ["algo", "index_name", "dataset", "k", "batch_size", "build_s",
            "search_s", "qps", "recall", "build_param", "search_param"]
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(cols)
        for r in results:
            w.writerow([r.algo, r.index_name, r.dataset, r.k, r.batch_size,
                        f"{r.build_s:.4f}", f"{r.search_s:.6f}", f"{r.qps:.1f}",
                        f"{r.recall:.4f}", json.dumps(r.build_param),
                        json.dumps(r.search_param)])


def pareto_frontier(results: List[BenchResult]) -> List[BenchResult]:
    """QPS/recall pareto points (the reference's plot module draws
    exactly this frontier)."""
    rows = sorted(results, key=lambda r: (-r.recall, -r.qps))
    front, best_qps = [], -1.0
    for r in rows:
        if r.qps > best_qps:
            front.append(r)
            best_qps = r.qps
    return list(reversed(front))
