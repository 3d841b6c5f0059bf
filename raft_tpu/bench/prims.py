"""Primitive micro-benchmarks — the ``cpp/bench/prims`` analog.

The reference ships gbench micro-benchmarks per primitive
(cpp/bench/prims: matrix/select_k.cu, distance, fused_l2_nn, kmeans...)
to ground kernel-choice heuristics in measurements. This module plays
that role for the TPU build: it times the competing implementations of
each hot primitive (XLA vs Pallas select_k; XLA-scan vs Pallas
fused_l2_nn; grouped vs per-query IVF scans) on the *current* backend,
so dispatch thresholds (`matrix/select_k.py` `_PALLAS_MIN_LEN`/
`_PALLAS_MAX_K`, `ivf_pq.search` scan_mode="auto") can be set
empirically rather than guessed.

CLI::

    python -m raft_tpu.bench.prims [select_k|fused_l2_nn|pairwise|
                                    kmeans|ivf_scan|all] [--csv out.csv]

Each row: {bench, params, impl, ms, throughput}.

Caveat: times are end-to-end per call (dispatch + execute + result
fetch), so the per-call host floor can swamp sub-ms kernels. For
per-op device time, chain iterations inside one jit with a data
dependency and difference two iteration counts — see
docs/tpu_design_notes.md for measured examples.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class PrimResult:
    bench: str
    impl: str
    ms: float
    throughput: float      # bench-specific unit/s (rows, pairs, queries)
    unit: str
    params: Dict[str, Any] = field(default_factory=dict)


def _time(fn: Callable[[], Any], iters: int = 10, warmup: int = 2) -> float:
    """Median wall ms of ``fn``, synchronized by fetching the result
    (``device_get``), matching bench/runner.py's end-to-end
    methodology."""
    for _ in range(warmup):
        jax.device_get(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.device_get(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


# ---------------------------------------------------------------------------
# select_k (reference: bench/prims/matrix/select_k.cu)
# ---------------------------------------------------------------------------

def bench_select_k(grid=None, iters: int = 10) -> List[PrimResult]:
    from raft_tpu.matrix import select_k as select_k_auto
    from raft_tpu.ops import select_k_pallas
    from raft_tpu.ops.pallas_kernels import _on_tpu

    if grid is None:
        grid = [(256, 2048, 10), (256, 16384, 10), (64, 65536, 10),
                (256, 16384, 64), (64, 65536, 64),
                # large-k tier (the reference's radix path covers
                # k ≤ 2048, select_radix.cuh): tiled two-phase vs the
                # full-sort fallback
                (64, 262144, 128), (64, 262144, 512), (256, 65536, 256)]
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    for batch, length, k in grid:
        s = jnp.asarray(rng.random((batch, length), dtype=np.float32))
        p = {"batch": batch, "len": length, "k": k}
        impls = {
            "lax.top_k": lambda: jax.lax.top_k(-s, k),
            "select_k.auto": lambda: select_k_auto(s, k),
        }
        if k > 64 and length >= 4 * 16384:
            impls["tiled.16k"] = lambda: select_k_auto(s, k,
                                                       len_tile=16384)
        if _on_tpu() and k <= 64:
            impls["pallas"] = lambda: select_k_pallas(s, k)
        for name, fn in impls.items():
            ms = _time(fn, iters)
            rows.append(PrimResult("select_k", name, ms,
                                   batch * 1e3 / ms, "rows/s", p))
    return rows


# ---------------------------------------------------------------------------
# fused_l2_nn (reference: bench/prims/distance/fused_l2_nn.cu)
# ---------------------------------------------------------------------------

def bench_fused_l2_nn(grid=None, iters: int = 10) -> List[PrimResult]:
    from raft_tpu.distance.fused_l2_nn import fused_l2_nn_argmin
    from raft_tpu.ops.pallas_kernels import _on_tpu

    if grid is None:
        grid = [(10000, 1024, 64), (10000, 16384, 128), (100000, 1024, 128)]
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    for m, n, d in grid:
        x = jnp.asarray(rng.random((m, d), dtype=np.float32))
        y = jnp.asarray(rng.random((n, d), dtype=np.float32))
        p = {"m": m, "n": n, "d": d}
        impls = {"xla": lambda: fused_l2_nn_argmin(x, y, impl="xla")}
        if _on_tpu():
            impls["pallas"] = lambda: fused_l2_nn_argmin(x, y, impl="pallas")
        for name, fn in impls.items():
            ms = _time(fn, iters)
            rows.append(PrimResult("fused_l2_nn", name, ms,
                                   m * 1e3 / ms, "rows/s", p))
    return rows


# ---------------------------------------------------------------------------
# pairwise distance (reference: bench/prims/distance/distance_*.cu)
# ---------------------------------------------------------------------------

def bench_pairwise(grid=None, iters: int = 10) -> List[PrimResult]:
    from raft_tpu.distance import pairwise_distance

    if grid is None:
        grid = [("sqeuclidean", 4096, 4096, 128), ("cosine", 4096, 4096, 128),
                ("l1", 2048, 2048, 128)]
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    for metric, m, n, d in grid:
        x = jnp.asarray(rng.random((m, d), dtype=np.float32))
        y = jnp.asarray(rng.random((n, d), dtype=np.float32))
        # reduce INSIDE the measured program: the [m, n] output is tens
        # of MB, and the device_get fence would otherwise time the
        # host-transfer, not the kernel (the sum blocks DCE; XLA may
        # fuse away the final HBM write, which a real consumer often
        # does too)
        f = jax.jit(lambda x_, y_, _mt=metric: jnp.sum(
            pairwise_distance(x_, y_, metric=_mt)))
        ms = _time(lambda: f(x, y), iters)
        rows.append(PrimResult(
            "pairwise", metric, ms, m * n * 1e3 / ms, "pairs/s",
            {"m": m, "n": n, "d": d, "metric": metric}))
    return rows


# ---------------------------------------------------------------------------
# kmeans Lloyd step (reference: bench/prims/cluster/kmeans.cu)
# ---------------------------------------------------------------------------

def bench_kmeans(grid=None, iters: int = 5) -> List[PrimResult]:
    from raft_tpu.cluster import KMeansParams, kmeans

    if grid is None:
        grid = [(100000, 64, 256), (100000, 128, 1024)]
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    for n, d, clusters in grid:
        x = jnp.asarray(rng.random((n, d), dtype=np.float32))
        p = KMeansParams(n_clusters=clusters, max_iter=5, seed=0)
        ms = _time(lambda: kmeans.fit(p, x), iters=iters, warmup=1)
        rows.append(PrimResult(
            "kmeans.fit5", "lloyd", ms, n * 5 * 1e3 / ms, "row-iters/s",
            {"n": n, "d": d, "clusters": clusters}))
    return rows


# ---------------------------------------------------------------------------
# IVF scan-mode crossover (grouped vs per-query; sets scan_mode="auto")
# ---------------------------------------------------------------------------

def bench_ivf_scan(batches=(16, 64, 256, 1024, 4096), n: int = 200_000,
                   d: int = 96, n_lists: int = 1024, n_probes: int = 20,
                   iters: int = 5) -> List[PrimResult]:
    from raft_tpu.neighbors import ivf_pq

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((n, d), dtype=np.float32))
    index = ivf_pq.build(x, ivf_pq.IndexParams(
        n_lists=n_lists, pq_dim=max(8, d // 2 // 8 * 8), seed=0))
    q_all = jnp.asarray(rng.random((max(batches), d), dtype=np.float32))
    rows: List[PrimResult] = []
    for b in batches:
        q = q_all[:b]
        for mode in ("grouped", "per_query"):
            sp = ivf_pq.SearchParams(n_probes=n_probes, scan_mode=mode)
            ms = _time(lambda: ivf_pq.search(index, q, 10, sp),
                       iters=iters, warmup=1)
            rows.append(PrimResult(
                "ivf_pq.scan", mode, ms, b * 1e3 / ms, "queries/s",
                {"batch": b, "n": n, "n_lists": n_lists,
                 "n_probes": n_probes}))
    return rows


# ---------------------------------------------------------------------------
# IVF-PQ scan kernels: XLA one-hot grouped scan vs fused Pallas LUT scan
# ---------------------------------------------------------------------------

def bench_pq_scan(grid=None, iters: int = 3) -> List[PrimResult]:
    """One-hot (XLA grouped) vs fused Pallas LUT-scan row per config —
    the measurement behind the ``scan_select="pallas"`` dispatch tier
    (reference: the compute_similarity kernel benches under
    cpp/bench/prims). The index is built WITHOUT the recon cache so the
    one-hot path actually pays its per-chunk decode, as the DEEP-100M
    regime does. Off-TPU the Pallas row runs in interpreter mode and its
    time is meaningless — it is kept tiny and flagged via params."""
    from raft_tpu.neighbors import ivf_common as ic
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.ivf_pq import packed_nbytes
    from raft_tpu.ops.pallas_kernels import (LUT_SCAN_BINS, _on_tpu,
                                             pallas_lut_scan_wanted)

    on_tpu = _on_tpu()
    if grid is None:
        # (n, d, n_lists, n_probes, k_cand, batch)
        grid = ([(200_000, 96, 512, 64, 400, 2000)] if on_tpu
                else [(4_000, 32, 16, 8, 40, 128)])
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    for n, d, n_lists, n_probes, k_cand, batch in grid:
        x = jnp.asarray(rng.random((n, d), dtype=np.float32))
        index = ivf_pq.build(x, ivf_pq.IndexParams(
            n_lists=n_lists, pq_dim=max(8, d // 2 // 8 * 8), seed=0,
            cache_reconstruction="never"))
        q = x[:batch]
        p = {"n": n, "d": d, "n_lists": n_lists, "n_probes": n_probes,
             "k_cand": k_cand, "batch": batch, "on_tpu": on_tpu}
        impls = {"one_hot": ivf_pq.SearchParams(
            n_probes=n_probes, scan_mode="grouped", scan_select="exact")}
        # gate the pallas row with search()'s FULL dispatch condition —
        # a declined request silently falls back, which would mislabel
        # this row: kernel layout/VMEM check, bin capacity for k_cand,
        # the HBM guard, and the codebook kind
        n_seg = ic.n_segments(batch * n_probes, n_lists, ic.SEGMENT_SIZE)
        lut_ok = (n_probes * LUT_SCAN_BINS >= k_cand
                  and index.codebook_kind == "per_subspace"
                  and ic.lut_scan_mem_ok(n_seg, ic.SEGMENT_SIZE,
                                         index.rot_dim, batch * n_probes,
                                         LUT_SCAN_BINS)
                  and pallas_lut_scan_wanted(
                      index.pq_dim, index.pq_book_size, index.pq_len,
                      packed_nbytes(index.pq_dim, index.pq_bits),
                      index.packed_codes.shape[-1], index.max_list_size,
                      index.rot_dim, lut_dtype="bfloat16"))
        if lut_ok:
            impls["pallas_lut"] = ivf_pq.SearchParams(
                n_probes=n_probes, scan_mode="grouped",
                scan_select="pallas", lut_dtype="bfloat16")
        for name, sp in impls.items():
            ms = _time(lambda: ivf_pq.search(index, q, k_cand, sp),
                       iters=iters, warmup=1)
            rows.append(PrimResult("ivf_pq.lut_scan", name, ms,
                                   batch * 1e3 / ms, "queries/s", p))
        # FILTERED rows (ISSUE 12 acceptance): the fused filtered scan
        # vs the forced-fallback tier the same filtered shape used to
        # pay (grouped XLA under RAFT_TPU_PALLAS_LUTSCAN=never) at 10%
        # selectivity — the cliff this PR removes, as one prims pair
        from raft_tpu.core import bitset as _bitset

        keep = rng.random(n) < 0.1
        fb = _bitset.from_mask(jnp.asarray(keep))
        pf = {**p, "filter_selectivity": 0.1}
        # the filtered gate re-checks the kernel admission with
        # filtered=True — the filter-byte slots + unpack selection
        # matrix grow the VMEM model, so a shape that fits unfiltered
        # can still decline filtered (search() would silently run the
        # approx tier and this row would be mislabeled)
        filtered_ok = (lut_ok
                       and ic.filtered_scan_mem_ok(
                           n_lists, index.max_list_size)
                       and pallas_lut_scan_wanted(
                           index.pq_dim, index.pq_book_size,
                           index.pq_len,
                           packed_nbytes(index.pq_dim, index.pq_bits),
                           index.packed_codes.shape[-1],
                           index.max_list_size, index.rot_dim,
                           lut_dtype="bfloat16", filtered=True))
        if filtered_ok:
            sp_f = ivf_pq.SearchParams(
                n_probes=n_probes, scan_mode="grouped",
                scan_select="pallas", lut_dtype="bfloat16")
            ms = _time(lambda: ivf_pq.search(index, q, k_cand, sp_f,
                                             filter_bitset=fb),
                       iters=iters, warmup=1)
            rows.append(PrimResult("ivf_pq.lut_scan",
                                   "filtered_pallas_lut", ms,
                                   batch * 1e3 / ms, "queries/s", pf))
        else:
            rows.append(PrimResult("ivf_pq.lut_scan",
                                   "filtered_pallas_skipped", 0.0, 0.0,
                                   "queries/s",
                                   {**pf, "skipped": "outside the "
                                    "kernel/HBM gate"}))
        from raft_tpu.bench.runner import _scoped_env

        with _scoped_env({"RAFT_TPU_PALLAS_LUTSCAN": "never"}):
            sp_u = ivf_pq.SearchParams(n_probes=n_probes,
                                       scan_mode="grouped",
                                       scan_select="approx")
            ms = _time(lambda: ivf_pq.search(index, q, k_cand, sp_u,
                                             filter_bitset=fb),
                       iters=iters, warmup=1)
            rows.append(PrimResult("ivf_pq.lut_scan",
                                   "filtered_fallback", ms,
                                   batch * 1e3 / ms, "queries/s", pf))
    return rows


# ---------------------------------------------------------------------------
# refine: XLA einsum-gather vs fused Pallas gather-refine
# ---------------------------------------------------------------------------

def bench_refine(grid=None, iters: int = 3) -> List[PrimResult]:
    """Einsum-gather XLA refine vs the fused Pallas gather-refine tier —
    the measurement behind ``neighbors.refine``'s dispatch (reference:
    the refinement kernels' gbench rows under cpp/bench/prims). Each
    impl is forced through the ``RAFT_TPU_PALLAS_REFINE`` override so a
    silent dispatch fallback cannot mislabel a row. The einsum row
    materializes the ``[m, C, d]`` gather buffer, so it only runs where
    that buffer is survivable; the batch-10000 × k_cand-2000 acceptance
    shape runs fused-only (its skipped einsum twin is recorded in
    params — at 7.7 GB the buffer IS the reason the tier exists, and a
    deliberately-OOMing row would kill the whole sweep). Off-TPU the
    pallas row runs in interpreter mode and its time is meaningless —
    kept tiny and flagged via params."""
    import os

    from raft_tpu.neighbors import refine as refine_mod
    from raft_tpu.ops.pallas_kernels import (_on_tpu,
                                             pallas_gather_refine_wanted)

    on_tpu = _on_tpu()
    if grid is None:
        # (n, d, m, k_cand, k)
        grid = ([(200_000, 96, 2_500, 2000, 10),
                 (200_000, 96, 10_000, 2000, 10)] if on_tpu
                else [(2_000, 32, 64, 256, 8)])
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    prev = os.environ.get("RAFT_TPU_PALLAS_REFINE")
    try:
        for n, d, m, C, k in grid:
            x = jnp.asarray(rng.random((n, d), dtype=np.float32))
            q = jnp.asarray(rng.random((m, d), dtype=np.float32))
            cand = jnp.asarray(
                rng.integers(0, n, (m, C)).astype(np.int32))
            buf_gib = m * C * d * 4 / 2**30
            p = {"n": n, "d": d, "m": m, "k_cand": C, "k": k,
                 "gather_buffer_gib": round(buf_gib, 2), "on_tpu": on_tpu}
            impls = {}
            if buf_gib <= 2.5:
                impls["einsum_gather"] = "never"
            else:
                p["einsum_skipped"] = (f"[m, C, d] buffer "
                                       f"{buf_gib:.1f} GiB")
            # gate the pallas row under the SAME force it will run with
            # (off-TPU the auto gate always declines, and an env value
            # left over from the previous impl must not leak into this
            # decision); skips are recorded, not silent
            os.environ["RAFT_TPU_PALLAS_REFINE"] = "always"
            if pallas_gather_refine_wanted(m, C, d, k):
                impls["pallas_gather"] = "always"
            else:
                p["pallas_skipped"] = "shape outside the kernel gate"
            for name, force in impls.items():
                os.environ["RAFT_TPU_PALLAS_REFINE"] = force
                ms = _time(lambda: refine_mod.refine(x, q, cand, k),
                           iters=iters, warmup=1)
                rows.append(PrimResult("refine", name, ms,
                                       m * 1e3 / ms, "queries/s", p))
            if not impls:
                rows.append(PrimResult("refine", "skipped", 0.0, 0.0,
                                       "queries/s", p))
    finally:
        if prev is None:
            os.environ.pop("RAFT_TPU_PALLAS_REFINE", None)
        else:
            os.environ["RAFT_TPU_PALLAS_REFINE"] = prev
    return rows


# ---------------------------------------------------------------------------
# tiered refine: HBM-resident vs host-prefetched vs serialized (ISSUE 17)
# ---------------------------------------------------------------------------

def bench_tiered_refine(grid=None, iters: int = 3) -> List[PrimResult]:
    """The memory-tiered refined search, three residency legs per
    config (reference claim: the host→HBM candidate-row fetch hides
    under the LUT scan):

    - ``hbm_resident``: the raw vectors live on device — the refine
      dispatch tiers run without any transfer (the ceiling);
    - ``tiered_prefetch``: host-resident base, candidate rows fetched
      by the :class:`~raft_tpu.neighbors.tiered.RowPrefetcher` pipeline
      overlapped under the scan (``refine_transfer="tiered"``);
    - ``serialized_gather``: the same host base through the serialized
      host gather (``refine_transfer="serial"``) — what the fetch costs
      when nothing hides it.

    Params carry the roofline context: ``h2d_gib`` (candidate rows
    crossing host→HBM per search) and, for the host legs, the
    effective ``h2d_gibps`` that wall implies, plus the tiered leg's
    hit/stall split (hits ≫ stalls is the overlap working). A config
    the mem guard declines records a ``tiered_skipped`` param instead
    of a silent hole."""
    import dataclasses

    from raft_tpu import obs
    from raft_tpu.neighbors import ivf_pq, tiered
    from raft_tpu.ops.pallas_kernels import _on_tpu

    on_tpu = _on_tpu()
    if grid is None:
        # (n, d, m, k)
        grid = ([(500_000, 96, 1024, 10)] if on_tpu
                else [(20_000, 32, 256, 10)])
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    for n, d, m, k in grid:
        x = rng.random((n, d), dtype=np.float32)
        x_dev = jnp.asarray(x)
        q = jnp.asarray(rng.random((m, d), dtype=np.float32))
        idx = ivf_pq.build(x_dev, ivf_pq.IndexParams(
            n_lists=64 if on_tpu else 16, pq_dim=min(d, 32), seed=0,
            cache_reconstruction="never"))
        base = ivf_pq.SearchParams(n_probes=16, refine="f32_regen",
                                   refine_ratio=4.0,
                                   lut_dtype="float32")
        k_cand = int(k * base.refine_ratio)
        h2d_gib = m * k_cand * d * 4 / 2**30
        p = {"n": n, "d": d, "m": m, "k": k, "k_cand": k_cand,
             "h2d_gib": round(h2d_gib, 4), "on_tpu": on_tpu,
             "pipeline_batch": tiered.pipeline_batch(m)}
        tiered_params = dataclasses.replace(base,
                                            refine_transfer="tiered")
        legs = [("hbm_resident", x_dev, base),
                ("serialized_gather", x,
                 dataclasses.replace(base, refine_transfer="serial"))]
        if tiered.tiered_refine_wanted(x, m, k_cand, d, tiered_params):
            legs.insert(1, ("tiered_prefetch", x, tiered_params))
        else:
            p["tiered_skipped"] = ("mem guard or shape declined the "
                                   "prefetch pipeline")
        for name, base_ds, params in legs:
            lp = dict(p)
            if name == "tiered_prefetch":
                # one un-timed pass with recording on: the hit/stall
                # split is the overlap evidence riding next to the wall
                reg = obs.MetricsRegistry()
                obs.enable(registry=reg, hbm=False)
                try:
                    ivf_pq.search(idx, q, k, params, dataset=base_ds)
                finally:
                    obs.disable()
                c = reg.snapshot()["counters"]
                lp["prefetch_hits"] = int(sum(
                    v for key, v in c.items()
                    if key.startswith("serve.prefetch.hit")))
                lp["prefetch_stalls"] = int(sum(
                    v for key, v in c.items()
                    if key.startswith("serve.prefetch.stall")))
            ms = _time(lambda: ivf_pq.search(idx, q, k, params,
                                             dataset=base_ds),
                       iters=iters, warmup=1)
            if name != "hbm_resident":
                lp["h2d_gibps"] = round(h2d_gib / (ms / 1e3), 3)
            rows.append(PrimResult("tiered_refine", name, ms,
                                   m * 1e3 / ms, "queries/s", lp))
    return rows


# ---------------------------------------------------------------------------
# build encode throughput: serial build_chunked vs the prefetch-
# overlapped distributed encode (ISSUE 13)
# ---------------------------------------------------------------------------

def bench_build_encode(grid=None, iters: int = 1) -> List[PrimResult]:
    """Serial ``build_chunked`` vs the distributed prefetch-overlapped
    encode — the measurement behind build-throughput (vectors/s/chip),
    ROADMAP item 2's first-class build metric. Three rows per config:

    - ``build_chunked``: the single-host serial walk (read → H2D →
      encode strictly in sequence), vectors/s;
    - ``distributed_serial``: the sharded walk with ``prefetch=False``
      (serialized copy-then-encode per shard) — the overlap baseline;
    - ``distributed_prefetch``: the same walk with the double-buffered
      host→HBM prefetcher — chunk N+1's read+transfer hidden under
      chunk N's encode. vectors/s/chip = n / wall / n_dev (the CPU-mesh
      emulation walks shards sequentially, so total wall ≈ n_dev × the
      per-shard wall a real pod would pay).

    The distributed rows need a ≥ 2-device mesh; a 1-device host
    records the skip instead of silently dropping the row. Each row
    carries the PR-9 roofline columns of the jitted per-chunk encode
    program (the pass's hot program), attributed from the measured
    per-chunk encode time."""
    import jax

    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs import prof as _prof

    n_dev = len(jax.devices())
    if grid is None:
        # (n, d, n_lists, chunk_rows)
        grid = [(60_000, 32, 16, 4096)]
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    for n, d, n_lists, chunk_rows in grid:
        x = rng.random((n, d), dtype=np.float32)
        params = ivf_pq.IndexParams(n_lists=n_lists,
                                    pq_dim=max(8, d // 2 // 8 * 8),
                                    kmeans_n_iters=4, seed=0,
                                    cache_reconstruction="never")
        p = {"n": n, "d": d, "n_lists": n_lists,
             "chunk_rows": chunk_rows, "n_dev": n_dev}

        # roofline attribution of the per-chunk encode program (the
        # walk's hot program; cost columns describe what the rows time)
        idx0 = ivf_pq.build(jnp.asarray(x[:4096]), params)
        xb = jnp.asarray(x[:chunk_rows])
        lb = jnp.zeros((chunk_rows,), jnp.int32)
        t_enc0 = time.perf_counter()
        jax.block_until_ready(ivf_pq._encode_with_norms(
            xb @ idx0.rotation.T, idx0.centers_rot, lb, idx0.codebooks,
            "per_subspace"))
        enc_s = time.perf_counter() - t_enc0
        cost = _prof.analyze_jit(
            lambda xb_, lb_: ivf_pq._encode_with_norms(
                xb_ @ idx0.rotation.T, idx0.centers_rot, lb_,
                idx0.codebooks, "per_subspace"),
            xb, lb, elapsed_s=enc_s)
        if cost is not None:
            p.update(flops=cost.flops, bytes_accessed=cost.bytes_accessed,
                     arith_intensity=cost.arithmetic_intensity,
                     bound=cost.bound)

        # untimed warm pass over a prefix: the first build at a shape
        # pays the jit compiles — without a warm-up they land in
        # whichever row runs first and the serial-vs-prefetch (and
        # chunked-vs-distributed) comparison measures compile cost
        warm_n = min(n, 4 * chunk_rows)
        ivf_pq.build_chunked(x[:warm_n], params, chunk_rows=chunk_rows)
        t0 = time.perf_counter()
        ivf_pq.build_chunked(x, params, chunk_rows=chunk_rows)
        wall = time.perf_counter() - t0
        rows.append(PrimResult("build_encode", "build_chunked",
                               wall * 1e3, n / wall, "vectors/s", p))
        if n_dev < 2:
            rows.append(PrimResult(
                "build_encode", "distributed_skipped", 0.0, 0.0,
                "vectors/s/chip",
                {**p, "skipped": f"{n_dev} device(s): no mesh axis to "
                                 "shard the build over"}))
            continue
        from raft_tpu.parallel import make_mesh

        mesh = make_mesh()
        ivf_pq.build_distributed(x, params, mesh=mesh,
                                 chunk_rows=chunk_rows, prefetch=False)
        for impl, prefetch in (("distributed_serial", False),
                               ("distributed_prefetch", True)):
            t0 = time.perf_counter()
            ivf_pq.build_distributed(x, params, mesh=mesh,
                                     chunk_rows=chunk_rows,
                                     prefetch=prefetch)
            wall = time.perf_counter() - t0
            rows.append(PrimResult(
                "build_encode", impl, wall * 1e3, n / wall / n_dev,
                "vectors/s/chip", p))
    return rows


def measure_merge_tier(mesh, x, q, k: int, tier: str, iters: int = 3,
                       schedule: Optional[str] = None,
                       with_cost: bool = False, axis="shard",
                       per_axis: bool = False):
    """Measure ONE cross-shard merge tier through sharded kNN on
    ``mesh``: returns ``(median ms per call, merge-phase comms bytes,
    cost)`` where ``cost`` is the PR-9 roofline attribution of the
    measured ring/merge program (an ``obs.prof.ProgramCost``, or
    ``None`` when ``with_cost`` is off or the closure won't lower).
    The single harness behind both the prims `ring_merge`/`hier_merge`
    rows and the dryrun's MULTICHIP scaling rows — byte-model or
    dispatch changes land in one place. Jits once so timed calls hit
    the cache (a bare ``sharded_knn`` call rebuilds its shard_map
    closure and re-traces every call — that would time the tracer),
    and enables a private registry only around the tracing call so the
    per-trace comms counters attribute exactly one merge.

    ``axis`` is forwarded to ``sharded_knn`` — pass the ``(outer,
    inner)`` tuple of a 2-D hier mesh to measure the ``hier`` tier (or
    the flat-ring comparator over the same two axes). With
    ``per_axis=True`` the bytes slot becomes a ``{axis_name: bytes}``
    dict split over the PR-19 per-axis attribution instead of one sum
    — how the scaling rows prove DCN traffic is O(k·pods).

    ``schedule`` env-forces the ring kernel's hop schedule
    (``RAFT_TPU_RING_OVERLAP``: "overlap" → on, "serial" → off) around
    BOTH the trace and the timed calls — the dispatch is read at trace
    time, so the force must cover the jit."""
    import os

    from raft_tpu import obs
    from raft_tpu.obs import spans as _spans
    from raft_tpu.obs.metrics import MetricsRegistry
    from raft_tpu.parallel import sharded_knn

    ops = {"ring": ("ring_topk",), "allgather": ("allgather",),
           "hier": ("ring_topk", "alltoall")}[tier]
    prev_env = os.environ.get("RAFT_TPU_RING_OVERLAP")
    if schedule is not None:
        os.environ["RAFT_TPU_RING_OVERLAP"] = (
            "on" if schedule == "overlap" else "off")
    try:
        fn = jax.jit(
            lambda xx, qq: sharded_knn(xx, qq, k, mesh, merge=tier,
                                       axis=axis))
        reg = MetricsRegistry()
        prev = _spans._state()  # a RAFT_TPU_OBS=1 enable must survive
        try:
            obs.enable(registry=reg, hbm=False)
            # the ONE trace: per-trace comms counters attribute exactly
            # one merge, and the AOT-compiled program below is what the
            # timed loop AND the cost attribution both use (PR-9 rule:
            # cost columns describe the measured program) — no second
            # trace, no second XLA compile
            compiled = fn.lower(x, q).compile()
        finally:
            _spans._restore(prev)
        c = reg.snapshot()["counters"]
        matched = [
            (key, v) for key, v in c.items()
            if key.startswith("comms.bytes{")
            and any(f"op={o}" in key for o in ops)]
        if per_axis:
            merge_bytes: Dict[str, int] = {}
            for key, v in matched:
                labels = dict(kv.split("=", 1) for kv
                              in key[key.index("{") + 1:-1].split(","))
                ax = labels.get("axis", "")
                merge_bytes[ax] = merge_bytes.get(ax, 0) + int(v)
        else:
            merge_bytes = int(sum(v for _, v in matched))
        ms = _time(lambda: compiled(x, q)[0], iters=iters, warmup=1)
        cost = None
        if with_cost:
            from raft_tpu.obs import prof as _prof

            try:
                cost = _prof.analyze_compiled(compiled,
                                              elapsed_s=ms / 1e3)
            except Exception:
                cost = None
    finally:
        if schedule is not None:
            if prev_env is None:
                os.environ.pop("RAFT_TPU_RING_OVERLAP", None)
            else:
                os.environ["RAFT_TPU_RING_OVERLAP"] = prev_env
    return ms, merge_bytes, cost


def bench_ring_merge(grid=None, iters: int = 3) -> List[PrimResult]:
    """Allgather-and-select vs the ring top-k exchange behind sharded
    search (``parallel.merge``) — the measurement grounding the merge
    tier's dispatch and the MULTICHIP scaling rows. Each row runs
    sharded kNN over the full local mesh with the merge tier forced,
    and decomposes the merge's interconnect cost from the PR-5
    ``comms.bytes`` counters (allgather: the materialized table; ring:
    n_dev−1 surviving-block hops). The ring tier measures BOTH hop
    schedules (``ring_serial`` = the PR-8 bulk-synchronous exchange,
    ``ring_overlap`` = the half-pipelined compute/comms-overlapped
    schedule, env-forced per row) plus the PR-9 roofline attribution
    of the measured ring program (flops/bytes/bound columns). Off-TPU
    the ring rides the ppermute fallback — identical schedule and
    identical counted bytes; wall time is CPU-mesh-shaped and the two
    schedule rows measure the same fallback program (the overlap is a
    kernel-internal property), so the comparison column is only
    load-bearing on real TPU rows."""
    from raft_tpu.parallel import make_mesh

    n_dev = len(jax.devices())
    if n_dev < 2:
        return [PrimResult("ring_merge", "skipped", 0.0, 0.0, "queries/s",
                           {"reason": f"{n_dev} device(s): no mesh axis "
                                      "to merge across"})]
    if grid is None:
        # (n, d, m, k)
        grid = [(32_768, 64, 1024, 10), (32_768, 64, 1024, 64)]
    mesh = make_mesh()
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    legs = (("allgather", "allgather", None),
            ("ring", "ring_serial", "serial"),
            ("ring", "ring_overlap", "overlap"))
    for n, d, m, k in grid:
        x = jnp.asarray(rng.random((n, d), dtype=np.float32))
        q = jnp.asarray(rng.random((m, d), dtype=np.float32))
        for tier, impl, schedule in legs:
            ms, merge_bytes, cost = measure_merge_tier(
                mesh, x, q, k, tier, iters=iters, schedule=schedule,
                with_cost=True)
            p = {"n": n, "d": d, "m": m, "k": k, "n_dev": n_dev,
                 "merge_bytes": merge_bytes}
            if schedule is not None:
                p["schedule"] = schedule
            if cost is not None:
                p.update(flops=cost.flops,
                         bytes_accessed=cost.bytes_accessed,
                         arith_intensity=cost.arithmetic_intensity,
                         bound=cost.bound)
            rows.append(PrimResult(
                "ring_merge", impl, ms, m * 1e3 / ms, "queries/s", p))
    return rows


def bench_hier_merge(grid=None, iters: int = 3) -> List[PrimResult]:
    """Flat single-ring vs the two-level ICI→DCN merge (ISSUE 19) on a
    2×(n_dev/2) hier mesh carved from the local devices. Both rows run
    the SAME sharded kNN over the same two mesh axes — only the merge
    tier differs — and decompose the merge's interconnect traffic into
    per-axis ``dcn_bytes``/``ici_bytes`` columns from the PR-19
    per-axis ``comms.bytes`` attribution. The load-bearing comparison
    is the DCN column: the flat ring drags whole surviving blocks
    across every hop including the slow cross-pod edges, while the
    hier tier's survivor exchange moves O(k·pods) rows — ``dcn_bytes``
    must sit strictly below the flat row's. Wall time is only
    meaningful on real multi-pod hardware (a CPU host mesh has no slow
    axis); the byte columns are layout-independent."""
    from raft_tpu.parallel import hier_mesh

    n_dev = len(jax.devices())
    if n_dev < 4 or n_dev % 2:
        return [PrimResult(
            "hier_merge", "skipped", 0.0, 0.0, "queries/s",
            {"reason": f"{n_dev} device(s): need an even mesh of >= 4 "
                       "to carve into pods"})]
    n_outer, n_inner = 2, n_dev // 2
    mesh = hier_mesh(n_inner, n_outer)
    axis = ("dcn", "ici")
    if grid is None:
        # (n, d, m, k)
        grid = [(32_768, 64, 1024, 10), (32_768, 64, 1024, 64)]
    rows: List[PrimResult] = []
    rng = np.random.default_rng(0)
    for n, d, m, k in grid:
        x = jnp.asarray(rng.random((n, d), dtype=np.float32))
        q = jnp.asarray(rng.random((m, d), dtype=np.float32))
        for tier, impl in (("ring", "flat_ring"), ("hier", "hier")):
            ms, by_axis, _ = measure_merge_tier(
                mesh, x, q, k, tier, iters=iters, axis=axis,
                per_axis=True)
            p = {"n": n, "d": d, "m": m, "k": k, "n_dev": n_dev,
                 "mesh": f"{n_outer}x{n_inner}",
                 "dcn_bytes": by_axis.get("dcn", 0),
                 "ici_bytes": by_axis.get("ici", 0)}
            rows.append(PrimResult(
                "hier_merge", impl, ms, m * 1e3 / ms, "queries/s", p))
    return rows


BENCHES: Dict[str, Callable[[], List[PrimResult]]] = {
    "select_k": bench_select_k,
    "fused_l2_nn": bench_fused_l2_nn,
    "pairwise": bench_pairwise,
    "kmeans": bench_kmeans,
    "ivf_scan": bench_ivf_scan,
    "pq_scan": bench_pq_scan,
    "refine": bench_refine,
    "tiered_refine": bench_tiered_refine,
    "ring_merge": bench_ring_merge,
    "hier_merge": bench_hier_merge,
    "build_encode": bench_build_encode,
}


def run(names=("all",)) -> List[PrimResult]:
    picked = list(BENCHES) if "all" in names else list(names)
    rows: List[PrimResult] = []
    for name in picked:
        if name not in BENCHES:
            raise ValueError(f"unknown bench {name!r} (have {sorted(BENCHES)})")
        rows.extend(BENCHES[name]())
    return rows


def export_csv(rows: List[PrimResult], path: str) -> None:
    import csv
    import json as _json

    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["bench", "impl", "ms", "throughput", "unit", "params"])
        for r in rows:
            w.writerow([r.bench, r.impl, f"{r.ms:.4f}",
                        f"{r.throughput:.1f}", r.unit, _json.dumps(r.params)])


def export_json(rows: List[PrimResult], path: str) -> None:
    """Self-describing record: rows + the same environment-provenance
    stamp bench rows carry (``runner.environment_stamp``), so prim
    measurements from different chips/jax builds are never compared as
    if they were the same machine."""
    import json as _json
    import time as _time

    from raft_tpu.bench.runner import environment_stamp

    doc = {
        "schema": "raft_tpu.prims/1",
        "measured_at": _time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                      _time.gmtime()),
        "env": environment_stamp(),
        "rows": [{"bench": r.bench, "impl": r.impl, "ms": r.ms,
                  "throughput": r.throughput, "unit": r.unit,
                  "params": r.params} for r in rows],
    }
    with open(path, "w") as f:
        _json.dump(doc, f, indent=1)


def main(argv: Optional[List[str]] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="raft_tpu prim micro-benchmarks")
    ap.add_argument("benches", nargs="*", default=["all"])
    ap.add_argument("--csv", default=None)
    ap.add_argument("--json", default=None,
                    help="write rows + environment-provenance stamp "
                         "as one JSON record")
    args = ap.parse_args(argv)
    from raft_tpu.bench.runner import environment_stamp

    env = environment_stamp()
    print(f"[prims] env: jax={env.get('jax')} backend={env.get('backend')} "
          f"{env.get('device_kind')} x{env.get('device_count')}")
    rows = run(args.benches or ["all"])
    for r in rows:
        print(f"{r.bench:14s} {r.impl:14s} {r.ms:10.3f} ms "
              f"{r.throughput:14,.0f} {r.unit:12s} {r.params}")
    if args.csv:
        export_csv(rows, args.csv)
    if args.json:
        export_json(rows, args.json)


if __name__ == "__main__":
    main()
