#!/usr/bin/env python
"""Chip smoke: the search path end to end on a TPU at hard-1M×128.

    python chip_smoke.py [--seed S]            # one chip
    python chip_smoke.py --chips 4 [--seed S]  # the sharded path on 4

Data: ``bench.dataset.make_synthetic_hard`` — 1,000,000 × 128 f32,
sqeuclidean, 10,000 queries, from ``--seed``. The exact top-10 of the
first 256 queries, computed with numpy on the host (independent of
raft_tpu), is the reference every phase's recall@10 is measured
against.

One chip, in this order, in one process:

1. ``brute_force.build`` + ``knn`` at batch 10,000: recall ≥ 0.999.
2. ``ivf_flat`` with bench.py's hard-leg build params (1024 lists,
   spill, cap factor 1.5), n_probes 64, approx select: recall ≥ 0.90.
3. ``ivf_pq`` with bench.py's params (pq_dim 64 as well), n_probes 64,
   ``refine_ratio`` 4, ``lut_dtype="auto"``: recall ≥ 0.90. The same
   search at ``refine_ratio`` 40 (400 candidates) follows: the fused
   gather-refine kernel's gate admits 400 candidates, not 40.
4. Served: the phase-3 index admitted to ``serve.IndexRegistry``
   behind a ``MicroBatchServer`` (``start()`` warms its buckets); 64
   single-query submits must equal a direct ``ivf_pq.search`` exactly.

Phases 1-3 run under ``obs`` with a fresh registry each, and the Pallas
tiers must engage: ``ivf_pq.scan`` on a Pallas impl, ``refine`` on
``pallas_gather`` at 400 candidates, and no ``select_k`` fallback.

``--chips 4`` runs only the sharded phase over a 4-chip mesh: the
sharded IVF-PQ build of the same base, ``search_ivf_pq`` at batch 2048
with the ring merge against the allgather merge (ids equal, the merge
dispatched ``ring_kernel``), and ``sharded_knn`` ring against
allgather. Every sharded operand must span 4 distinct devices.

Each phase prints one line as it ends (``phase <name> {json}``: device
kind, build / first-call / steady-call walls, recall, peak HBM so far,
dispatch counters) — informational, not metrics. The first call
includes compilation. The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
Any failure raises: the exit code is non-zero and that line is never
printed. The script needs a TPU; on any other platform it exits
non-zero naming the platform. The compile cache follows
``JAX_COMPILATION_CACHE_DIR``, else the repo's ``.cache/jax``.
"""

import argparse
import dataclasses
import json
import time

import numpy as np


class SmokeFailure(AssertionError):
    """A phase produced a wrong answer or a kernel gave way."""


@dataclasses.dataclass(frozen=True)
class Size:
    """Scale of one run. The defaults are the real hard-1M×128 cell;
    tests rehearse the same phases on a much smaller one."""

    n: int = 1_000_000
    dim: int = 128
    n_queries: int = 10_000
    n_ref: int = 256
    k: int = 10
    batch: int = 10_000
    n_lists: int = 1024
    n_probes: int = 64
    pq_dim: int = 64
    n_served: int = 64
    sharded_batch: int = 2048


FULL = Size()
# the Pallas scan tiers of ivf_pq.search (ivf_pq.scan.dispatch impl)
PALLAS_SCANS = ("pallas_lut", "segk", "grouped_pallas")


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def make_data(size: Size, seed: int):
    from raft_tpu.bench.dataset import make_synthetic_hard

    ds = make_synthetic_hard("hard", size.n, size.dim, size.n_queries,
                             seed=seed)
    return ds.base, ds.queries


def exact_topk(base: np.ndarray, queries: np.ndarray, k: int,
               block: int = 1 << 17) -> np.ndarray:
    """Exact sqeuclidean top-k ids by plain numpy in float64."""
    q = queries.astype(np.float64)
    qn = np.sum(q * q, axis=1)
    best_d = np.full((len(q), 0), np.inf)
    best_i = np.zeros((len(q), 0), np.int64)
    for a in range(0, len(base), block):
        b = base[a:a + block].astype(np.float64)
        d = qn[:, None] + np.sum(b * b, axis=1)[None, :] - 2.0 * (q @ b.T)
        cd = np.concatenate([best_d, d], axis=1)
        ci = np.concatenate(
            [best_i, np.broadcast_to(np.arange(a, a + len(b)), d.shape)],
            axis=1)
        top = np.argpartition(cd, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(cd, top, axis=1)
        best_i = np.take_along_axis(ci, top, axis=1)
    order = np.argsort(best_d, axis=1, kind="stable")
    return np.take_along_axis(best_i, order, axis=1)


def recall(ids, ref: np.ndarray) -> float:
    got = np.asarray(ids)[:len(ref)]
    hits = sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(got, ref))
    return hits / ref.size


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _counters(reg):
    return {key: val for key, val in reg.snapshot()["counters"].items()
            if ".dispatch" in key or ".fallback" in key}


def _dispatched(counters, name: str):
    """impl labels of ``<name>.dispatch{impl=...}`` counters."""
    prefix = name + ".dispatch{"
    out = set()
    for key in counters:
        if key.startswith(prefix):
            labels = dict(kv.split("=", 1)
                          for kv in key[len(prefix):-1].split(","))
            out.add(labels["impl"])
    return out


def report(name: str, **fields) -> dict:
    import jax

    row = {"device_kind": jax.devices()[0].device_kind, **fields}
    print(f"phase {name} {json.dumps(row, sort_keys=True)}", flush=True)
    return row


def run_phase(name, build, search, ref, bar, **extra):
    """Build, then call ``search(index)`` twice (the first call
    compiles), under a fresh obs registry; check recall@k ≥ ``bar`` and
    that no select_k row the kernel serves took another engine."""
    from raft_tpu import obs

    reg = obs.MetricsRegistry()
    obs.enable(registry=reg, hbm=False)
    try:
        index, build_s = _timed(build)
        _, first_s = _timed(lambda: search(index))
        (_, ids), steady_s = _timed(lambda: search(index))
    finally:
        obs.disable()
    counters = _counters(reg)
    got = recall(ids, ref)
    row = report(name, build_s=build_s, first_call_s=first_s,
                 steady_call_s=steady_s, recall=got,
                 peak_bytes_in_use=_peak_bytes(), counters=counters,
                 **extra)
    require(got >= bar, f"{name}: recall@{ref.shape[1]} {got} < {bar}")
    require(not any(key.startswith("select_k.fallback")
                    for key in counters),
            f"{name}: a select_k row the kernel serves took another "
            f"engine: {counters}")
    return index, row


def phase_brute_force(base, queries, ref, size: Size):
    from raft_tpu.neighbors import brute_force

    q = queries[:size.batch]
    _, row = run_phase(
        "brute_force",
        lambda: brute_force.build(base, metric="sqeuclidean"),
        lambda index: brute_force.knn(index, q, size.k),
        ref, 0.999)
    return row


def phase_ivf_flat(base, queries, ref, size: Size):
    from raft_tpu.neighbors import ivf_flat

    q = queries[:size.batch]
    params = ivf_flat.IndexParams(n_lists=size.n_lists,
                                  metric="sqeuclidean", spill=True,
                                  list_size_cap_factor=1.5)
    sp = ivf_flat.SearchParams(n_probes=size.n_probes, scan_select="approx")
    _, row = run_phase(
        "ivf_flat", lambda: ivf_flat.build(base, params),
        lambda index: ivf_flat.search(index, q, size.k, sp), ref, 0.90)
    return row


def pq_search_params(size: Size, refine_ratio: float):
    from raft_tpu.neighbors import ivf_pq

    return ivf_pq.SearchParams(n_probes=size.n_probes, scan_select="approx",
                               lut_dtype="auto", refine="f32_regen",
                               refine_ratio=refine_ratio)


def phase_ivf_pq(base, queries, ref, size: Size):
    """IVF-PQ + refine; returns the index for the served phase."""
    from raft_tpu.neighbors import ivf_pq

    q = queries[:size.batch]
    params = ivf_pq.IndexParams(n_lists=size.n_lists, pq_dim=size.pq_dim,
                                metric="sqeuclidean", spill=True,
                                list_size_cap_factor=1.5)
    index, row = run_phase(
        "ivf_pq_refine4", lambda: ivf_pq.build(base, params),
        lambda idx: ivf_pq.search(idx, q, size.k,
                                  pq_search_params(size, 4.0),
                                  dataset=base),
        ref, 0.90)
    scans = _dispatched(row["counters"], "ivf_pq.scan")
    require(scans and scans <= set(PALLAS_SCANS),
            f"ivf_pq.scan took {sorted(scans)}, not a Pallas tier "
            f"{PALLAS_SCANS}")
    _, row40 = run_phase(
        "ivf_pq_refine40", lambda: index,
        lambda idx: ivf_pq.search(idx, q, size.k,
                                  pq_search_params(size, 40.0),
                                  dataset=base),
        ref, 0.90)
    refines = _dispatched(row40["counters"], "refine")
    require(refines == {"pallas_gather"},
            f"refine of {40 * size.k} candidates took {sorted(refines)}, "
            "not pallas_gather")
    return index, [row, row40]


def phase_served(index, base, queries, size: Size):
    """64 single-query submits through the micro-batch server must equal
    a direct ivf_pq.search of the same queries exactly."""
    from raft_tpu import serve
    from raft_tpu.neighbors import ivf_pq

    params = pq_search_params(size, 4.0)
    q = np.asarray(queries[:size.n_served])
    registry = serve.IndexRegistry()
    registry.admit("hard", index, params=params, dataset=base,
                   default_k=size.k)
    server = serve.MicroBatchServer(
        registry, serve.ServerConfig(default_slo_s=None))
    t0 = time.perf_counter()
    server.start()
    warm_s = time.perf_counter() - t0
    try:
        t0 = time.perf_counter()
        futures = [server.submit("hard", row) for row in q]
        served = np.stack([f.result(timeout=600)[1] for f in futures])
        serve_s = time.perf_counter() - t0
    finally:
        server.stop()
    _, direct = ivf_pq.search(index, q, size.k, params, dataset=base)
    direct = np.asarray(direct)
    mismatched = int(np.sum(np.any(served != direct, axis=1)))
    row = report("served", warmup_s=warm_s, serve_s=serve_s,
                 requests=len(q), mismatched_rows=mismatched,
                 peak_bytes_in_use=_peak_bytes())
    require(mismatched == 0,
            f"served ids differ from the direct search on {mismatched} "
            f"of {len(q)} queries")
    return row


def _devices_of(arr):
    return {shard.device for shard in arr.addressable_shards}


def phase_sharded(base, queries, ref, size: Size, devices=None):
    """Sharded IVF-PQ and sharded kNN over a mesh of ``devices``
    (default: all): ring merge against allgather merge."""
    from raft_tpu import obs, parallel
    from raft_tpu.neighbors import ivf_pq

    mesh = parallel.make_mesh(devices=devices)
    n_dev = mesh.devices.size
    q = queries[:size.sharded_batch]
    base_sh = parallel.shard_rows(base, mesh)
    # the sharded build has no spill: at the single-chip cap factor
    # (1.5) its fuller lists would drop rows, so it keeps the default cap
    params = ivf_pq.IndexParams(n_lists=size.n_lists, pq_dim=size.pq_dim,
                                metric="sqeuclidean")
    index, build_s = _timed(
        lambda: parallel.build_ivf_pq(params, base_sh, mesh))
    for name in ("packed_codes", "packed_ids", "packed_norms",
                 "list_sizes"):
        got = _devices_of(getattr(index, name))
        require(len(got) == n_dev,
                f"sharded index {name} spans {len(got)} devices, not "
                f"{n_dev}")
    require(len(_devices_of(base_sh)) == n_dev,
            f"sharded base spans {len(_devices_of(base_sh))} devices")
    sp = pq_search_params(size, 4.0)
    rows = []
    out = {}
    for merge in ("ring", "allgather"):
        reg = obs.MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            _, first_s = _timed(lambda: parallel.search_ivf_pq(
                sp, index, q, size.k, mesh, dataset=base_sh, merge=merge))
            (_, ids), steady_s = _timed(lambda: parallel.search_ivf_pq(
                sp, index, q, size.k, mesh, dataset=base_sh, merge=merge))
        finally:
            obs.disable()
        counters = _counters(reg)
        out[merge] = np.asarray(ids)
        rows.append(report(f"sharded_ivf_pq_{merge}", n_dev=n_dev,
                           build_s=build_s, first_call_s=first_s,
                           steady_call_s=steady_s,
                           recall=recall(ids, ref),
                           peak_bytes_in_use=_peak_bytes(),
                           counters=counters))
        if merge == "ring":
            impls = _dispatched(counters, "parallel.merge")
            require(impls == {"ring_kernel"},
                    f"the ring merge dispatched {sorted(impls)}, not "
                    "ring_kernel")
    require(np.array_equal(out["ring"], out["allgather"]),
            "sharded IVF-PQ: ring-merge ids differ from allgather-merge "
            "ids")
    require(rows[0]["recall"] >= 0.90,
            f"sharded IVF-PQ recall {rows[0]['recall']} < 0.90")
    knn = {}
    for merge in ("ring", "allgather"):
        (_, ids), wall_s = _timed(lambda: parallel.sharded_knn(
            base_sh, q, size.k, mesh, merge=merge))
        knn[merge] = np.asarray(ids)
        rows.append(report(f"sharded_knn_{merge}", n_dev=n_dev,
                           first_call_s=wall_s, recall=recall(ids, ref),
                           peak_bytes_in_use=_peak_bytes()))
    require(np.array_equal(knn["ring"], knn["allgather"]),
            "sharded kNN: ring-merge ids differ from allgather-merge ids")
    require(rows[-1]["recall"] >= 0.999,
            f"sharded kNN recall {rows[-1]['recall']} < 0.999")
    return rows


def run(chips: int, seed: int, size: Size = FULL) -> None:
    import jax.numpy as jnp

    base_np, queries_np = make_data(size, seed)
    ref = exact_topk(base_np, queries_np[:size.n_ref], size.k)
    base = jnp.asarray(base_np)
    queries = jnp.asarray(queries_np)
    if chips == 4:
        phase_sharded(base, queries, ref, size)
        return
    phase_brute_force(base, queries, ref, size)
    phase_ivf_flat(base, queries, ref, size)
    index, _ = phase_ivf_pq(base, queries, ref, size)
    phase_served(index, base, queries, size)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from raft_tpu.core.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found platform "
                         f"{platform!r}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} TPU "
                         f"devices; JAX found {len(devices)}")
    run(args.chips, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
