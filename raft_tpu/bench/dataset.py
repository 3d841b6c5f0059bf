"""Benchmark dataset management — .fbin/.ibin files, synthetic sets,
groundtruth.

TPU-native counterpart of the reference's bench dataset layer
(cpp/bench/ann/src/common/dataset.hpp: BinFile header/read/subset;
python/raft-ann-bench get_dataset/split_groundtruth).  Binary IO goes
through the native C++ reader (raft_tpu.native) with a numpy fallback.

A dataset directory holds::

    <name>/base.fbin           # [n, d] float32 vectors
    <name>/query.fbin          # [m, d] float32 queries
    <name>/groundtruth.ibin    # [m, k_gt] int32 exact neighbor ids
    <name>/groundtruth_dist.fbin  # [m, k_gt] float32 (optional)
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

import numpy as np

from .. import native


@dataclass
class Dataset:
    name: str
    base: np.ndarray        # [n, d] f32
    queries: np.ndarray     # [m, d] f32
    groundtruth: Optional[np.ndarray] = None  # [m, k_gt] i32
    metric: str = "sqeuclidean"

    @property
    def n(self) -> int:
        return self.base.shape[0]

    @property
    def dim(self) -> int:
        return self.base.shape[1]


def write_dataset(root: str, ds: Dataset) -> str:
    d = os.path.join(root, ds.name)
    os.makedirs(d, exist_ok=True)
    native.bin_write(os.path.join(d, "base.fbin"), ds.base.astype(np.float32))
    native.bin_write(os.path.join(d, "query.fbin"), ds.queries.astype(np.float32))
    if ds.groundtruth is not None:
        native.bin_write(os.path.join(d, "groundtruth.ibin"),
                         ds.groundtruth.astype(np.int32))
    return d


def bin_memmap(path: str, dtype) -> np.ndarray:
    """Memory-map a .fbin/.ibin file's payload as [n, d] without reading
    it (the reference's mmap path for billion-scale files,
    cpp/bench/ann/src/common/dataset.hpp BinFile::map). Row chunks are
    paged in on access and reclaimable — host RSS stays O(touched)."""
    n, d = native.bin_header(path)
    return np.memmap(path, dtype=np.dtype(dtype), mode="r", offset=8,
                     shape=(n, d))


def load_dataset(root: str, name: str, metric: str = "sqeuclidean",
                 max_rows: int = -1, mmap: bool = False) -> Dataset:
    """Load a dataset directory; ``max_rows`` subsets the base file and
    ``mmap=True`` memory-maps it instead of reading it whole (the
    reference's subset/memmap path for billion-scale files)."""
    d = os.path.join(root, name)
    if mmap:
        base = bin_memmap(os.path.join(d, "base.fbin"), np.float32)
        if max_rows >= 0:
            base = base[:max_rows]
    else:
        base = native.bin_read(os.path.join(d, "base.fbin"), np.float32,
                               count=max_rows)
    queries = native.bin_read(os.path.join(d, "query.fbin"), np.float32)
    gt_path = os.path.join(d, "groundtruth.ibin")
    gt = native.bin_read(gt_path, np.int32) if os.path.exists(gt_path) else None
    if gt is not None and 0 <= max_rows < native.bin_header(
            os.path.join(d, "base.fbin"))[0]:
        # the on-disk groundtruth covers the FULL base; against a subset
        # it contains unreachable ids and would deflate recall silently —
        # drop it so callers recompute on the subset
        gt = None
    return Dataset(name=name, base=base, queries=queries, groundtruth=gt,
                   metric=metric)


def make_synthetic(name: str, n: int, dim: int, n_queries: int,
                   metric: str = "sqeuclidean", seed: int = 0,
                   clustered: bool = True, hard: bool = False) -> Dataset:
    """Synthetic benchmark set shaped like the reference's standard ones
    (SIFT-style clustered f32).

    ``hard=True`` selects :func:`make_synthetic_hard` — many tiny
    clusters whose top-k sets cross kmeans cells, so IVF recall curves
    bend like real SIFT's instead of saturating."""
    if hard:
        return make_synthetic_hard(name, n, dim, n_queries, metric=metric,
                                   seed=seed)
    rng = np.random.default_rng(seed)
    if clustered:
        n_centers = max(16, int(np.sqrt(n)))
        centers = rng.random((n_centers, dim), dtype=np.float32) * 10.0
        assign = rng.integers(0, n_centers, n)
        base = centers[assign] + 0.5 * rng.standard_normal((n, dim), dtype=np.float32)
        q_assign = rng.integers(0, n_centers, n_queries)
        queries = centers[q_assign] + 0.5 * rng.standard_normal(
            (n_queries, dim), dtype=np.float32)
    else:
        base = rng.random((n, dim), dtype=np.float32)
        queries = rng.random((n_queries, dim), dtype=np.float32)
    return Dataset(name=name, base=base, queries=queries, metric=metric)


def make_synthetic_hard(name: str, n: int, dim: int, n_queries: int,
                        metric: str = "sqeuclidean", seed: int = 0,
                        rows_per_cluster: int = 24,
                        sigma: float = 0.45) -> Dataset:
    """Hard clustered synthetic: MANY tiny clusters, so every query's
    top-k must cross cluster/cell boundaries.

    The default :func:`make_synthetic` places ~√n Gaussian balls ~8×
    farther apart than their radius — a kmeans partition separates them
    perfectly and IVF recall saturates at tiny n_probes (0.9991 at
    n_probes=16 where real SIFT-1M needs far more). Two
    harder designs measured FLAT recall-vs-probes curves and were
    rejected: low-LID manifold clusters (foreign clusters' subspace
    arms hold neighbors whose centers rank arbitrarily far — a fixed
    fraction is unreachable at any probe count) and heavier uniform
    overlap (same mechanism). What reproduces real datasets' RISING,
    bending curve (measured 0.37→0.86 over n_probes 4→64 on a 200K
    proxy) is ``n / rows_per_cluster`` tiny clusters: a query's own
    cluster holds only ~``rows_per_cluster`` of its top-k, the rest
    come from ADJACENT clusters whose kmeans cells are ranked by
    center distance — exactly the structure probe counts pay for.

    ``sigma``: cluster radius as a fraction of the nearest-other-center
    distance (difficulty knob — bigger = more boundary crossing).
    Queries are drawn from the same distribution (the ann-benchmarks
    convention).
    """
    rng = np.random.default_rng(seed)
    n_centers = max(64, n // rows_per_cluster)
    centers = rng.standard_normal((n_centers, dim)).astype(np.float32)
    # nearest-other-center distance sets the radius scale (sample-
    # estimate on a subset to stay O(C·S)). Self pairs are masked BY
    # INDEX and the matrix computed in f64: the f32 expanded form's
    # cancellation error (~1e-3 at |c|²≈128) dwarfs a value threshold,
    # and a center "nearest to itself" gets scale ≈ 0 — its whole
    # cluster collapses into a point mass of exact ties (measured:
    # recall pinned at 0.61 at every n_probes)
    sel = rng.choice(n_centers, min(n_centers, 256), replace=False)
    sub = centers[sel].astype(np.float64)
    c64 = centers.astype(np.float64)
    d2 = (np.sum(c64**2, 1)[:, None] + np.sum(sub**2, 1)[None, :]
          - 2.0 * c64 @ sub.T)
    np.clip(d2, 0, None, out=d2)
    d2[np.arange(n_centers)[:, None] == sel[None, :]] = np.inf
    nearest = np.sqrt(d2.min(axis=1)).astype(np.float32)  # [C]
    # per-dim σ so a point's distance to its center ≈ sigma · nearest
    s = (sigma * nearest / np.sqrt(dim)).astype(np.float32)

    def sample(m, assign):
        return (centers[assign] + s[assign][:, None]
                * rng.standard_normal((m, dim)).astype(np.float32))

    assign = rng.integers(0, n_centers, n)
    base = sample(n, assign)
    q_assign = rng.integers(0, n_centers, n_queries)
    queries = sample(n_queries, q_assign)
    return Dataset(name=name, base=base, queries=queries, metric=metric)


class DeviceSyntheticChunks:
    """Deterministic clustered synthetic dataset materialized ON DEVICE
    in row chunks.

    Streaming a 38 GB base file from the host costs a full transfer PER
    PASS, while regenerating the same rows on-chip costs ~3 s per 1M
    rows — so billion-scale *synthetic* benchmarks (the DEEP-100M
    protocol shape) generate each chunk from (seed, row offset) on the
    device instead of reading a file. Every chunk is a pure function of
    the seed, so label/encode/groundtruth passes all see identical
    data; ``write_int8`` persists an SQ8 copy for the host-side refine
    gather (4× smaller than f32).

    Duck-types the slices build_chunked/compute_groundtruth take:
    ``shape``, ``provider[a:b] -> jax.Array`` (device), and
    ``sample_rows(sorted_idx)`` for trainset subsampling.
    """

    def __init__(self, n: int, dim: int, n_centers: int = 10_000,
                 seed: int = 7, std: float = 0.5, scale: float = 10.0,
                 chunk_rows: int = 1 << 20):
        import jax
        import jax.numpy as jnp

        self.shape = (n, dim)
        self.dtype = np.float32
        self.nbytes = n * dim * 4  # logical size (never materialized)
        self.chunk_rows = chunk_rows
        self._n_centers = n_centers
        self._std = std
        key = jax.random.PRNGKey(seed)
        ckey, self._akey, self._qkey = jax.random.split(key, 3)
        self.centers = jax.jit(
            lambda k: jax.random.uniform(k, (n_centers, dim)) * scale)(ckey)

        import functools

        @functools.partial(jax.jit, static_argnames=("m",))
        def gen(centers, akey, start, m):
            kk = jax.random.fold_in(akey, start)
            k1, k2 = jax.random.split(kk)
            assign = jax.random.randint(k1, (m,), 0, n_centers)
            return (centers[assign]
                    + std * jax.random.normal(k2, (m, dim), jnp.float32))

        self._gen = gen

    def _block(self, bi: int):
        """Internal FIXED-size generation block ``bi`` — row content is a
        function of the block index alone, so consumers slicing with any
        chunk size see identical rows (a start-offset-keyed generator
        would silently give different data per chunking)."""
        a = bi * self.chunk_rows
        m = min(self.chunk_rows, self.shape[0] - a)
        return self._gen(self.centers, self._akey, a, m)

    def __getitem__(self, sl):
        import jax.numpy as jnp

        if not isinstance(sl, slice):
            raise TypeError("DeviceSyntheticChunks supports slice access only")
        a = sl.start or 0
        b = min(sl.stop if sl.stop is not None else self.shape[0],
                self.shape[0])
        c = self.chunk_rows
        parts = []
        for bi in range(a // c, -(-b // c)):
            blk = self._block(bi)
            lo = max(a - bi * c, 0)
            hi = min(b - bi * c, blk.shape[0])
            parts.append(blk[lo:hi])
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    def queries(self, m: int):
        """Deterministic query set from a SEPARATE key branch of the
        root split — a fold_in of the row key at any offset can collide
        with a base block's key when chunk_rows divides it, silently
        making queries bit-identical to base rows (and recall trivial)."""
        return self._gen(self.centers, self._qkey, 0, m)

    def sample_rows(self, idx: np.ndarray):
        """Gather arbitrary (sorted) rows by regenerating the covering
        chunks on device — the trainset subsample path."""
        import jax.numpy as jnp

        import time as _t

        idx = np.asarray(idx)
        out = []
        c = self.chunk_rows
        t0 = _t.time()
        n_blocks = -(-self.shape[0] // c)
        for bi, a in enumerate(range(0, self.shape[0], c)):
            b = min(a + c, self.shape[0])
            local = idx[(idx >= a) & (idx < b)] - a
            if len(local):
                out.append(self[a:b][jnp.asarray(local)])
            if bi % 25 == 24:
                print(f"[sample_rows] block {bi + 1}/{n_blocks} "
                      f"({_t.time() - t0:.0f}s)", flush=True)
        return jnp.concatenate(out, axis=0)

    def write_int8(self, path: str, progress: bool = False):
        """Persist an SQ8 copy (for host-side refine gathers) +
        (scale, zero) dequant vectors. Returns (scale, zero)."""
        import struct

        import jax
        import jax.numpy as jnp

        n, d = self.shape
        # quantization range from one chunk (same distribution everywhere)
        x0 = self[0:min(n, self.chunk_rows)]
        mn = np.asarray(jnp.min(x0, axis=0))
        mx = np.asarray(jnp.max(x0, axis=0))
        zero = ((mn + mx) / 2).astype(np.float32)
        scale = np.maximum((mx - mn) / 254.0, 1e-12).astype(np.float32)
        zj, sj = jnp.asarray(zero), jnp.asarray(scale)

        @jax.jit
        def quant(x):
            return jnp.clip(jnp.round((x - zj) / sj), -127, 127
                            ).astype(jnp.int8)

        with open(path, "wb") as f:
            f.write(struct.pack("<ii", n, d))
            for a in range(0, n, self.chunk_rows):
                b = min(a + self.chunk_rows, n)
                f.write(np.asarray(jax.device_get(
                    quant(self[a:b]))).tobytes())
                if progress and a % (8 * self.chunk_rows) == 0:
                    print(f"[write_int8] {b}/{n}", flush=True)
        np.save(path + ".dequant.npy", np.stack([scale, zero]))
        return scale, zero


def compute_groundtruth(ds: Dataset, k: int = 100,
                        device_budget: int = 2 << 30,
                        chunk_rows: int = 1 << 18,
                        max_queries: int = 0,
                        device_base=None) -> Dataset:
    """Exact top-k groundtruth via the library's own brute force (the
    reference's split_groundtruth uses its GPU brute force the same way).

    Bases larger than ``device_budget`` bytes (memmapped billion-scale
    files) stream through the device in ``chunk_rows`` blocks with a
    running top-k merge — the base never materializes in HBM.
    ``max_queries`` bounds the GT query count (chunked GT costs one
    full-dataset pass; recall on a subset is standard at 10⁸ scale)."""
    import jax
    import jax.numpy as jnp

    queries = ds.queries
    if max_queries and queries.shape[0] > max_queries:
        queries = queries[:max_queries]
    if ds.base.nbytes <= device_budget or device_base is not None:
        from ..neighbors import brute_force

        # callers that already hold the base on device pass it in —
        # a second multi-GB copy has OOMed wide-dataset runs
        base_dev = (device_base if device_base is not None
                    else jnp.asarray(ds.base))
        index = brute_force.build(base_dev, metric=ds.metric)
        # impl="sort": groundtruth must be GUARANTEED exact — the default
        # strided-bin tile cut is only probabilistically exact (loses a
        # true neighbor when ≥3 top-k rows collide in one stride bin),
        # and every recall number in the bench is measured against this
        _, ids = brute_force.knn(index, jnp.asarray(queries), k,
                                 impl="sort")
        ds.groundtruth = np.asarray(ids, np.int32)
        del index
        return ds

    from ..core.errors import expects
    from ..distance.types import DistanceType, resolve_metric

    mt = resolve_metric(ds.metric)
    ip = mt == DistanceType.InnerProduct
    cos = mt == DistanceType.CosineExpanded
    expects(mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                   DistanceType.InnerProduct, DistanceType.CosineExpanded),
            "streaming groundtruth supports l2/ip/cosine, not %s",
            ds.metric)

    def _norm(v):
        return v / jnp.sqrt(jnp.maximum(
            jnp.sum(v * v, axis=-1, keepdims=True), 1e-30))

    q = jnp.asarray(np.asarray(queries, np.float32))
    if cos:  # cosine ranks as L2 on normalized rows
        q = _norm(q)
    m = q.shape[0]
    qt = 1024  # query tile: bounds the [qt, chunk] distance block

    n_rows = ds.base.shape[0]

    @jax.jit
    def merge_chunk(best_v, best_i, xb, base_id):
        x_sq = jnp.sum(xb * xb, axis=1)
        col_id = base_id + jnp.arange(xb.shape[0], dtype=jnp.int32)

        def tile(args):
            bv, bi, qv = args                       # [qt,k],[qt,k],[qt,d]
            s = jax.lax.dot_general(
                qv, xb, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)  # [qt, C]
            # rank key only: q² is dropped, so x² − 2qx is legitimately
            # negative near the query — no zero clamp here
            d2 = -s if ip else x_sq[None, :] - 2.0 * s
            d2 = jnp.where(col_id[None, :] < n_rows, d2, jnp.inf)
            v, p = jax.lax.top_k(-d2, k)
            ids = (base_id + p).astype(jnp.int32)
            mv = jnp.concatenate([bv, -v], axis=1)
            mi = jnp.concatenate([bi, ids], axis=1)
            vv, pp = jax.lax.top_k(-mv, k)
            return -vv, jnp.take_along_axis(mi, pp, axis=1)

        n_t = best_v.shape[0] // qt
        bv, bi = jax.lax.map(tile, (best_v.reshape(n_t, qt, k),
                                    best_i.reshape(n_t, qt, k),
                                    q_pad.reshape(n_t, qt, -1)))
        return bv.reshape(-1, k), bi.reshape(-1, k)

    # d2 drops q² (constant per query row — rank-safe); the candidate
    # x² term stays, it differs across base rows
    m_pad = -(-m // qt) * qt
    q_pad = jnp.pad(q, ((0, m_pad - m), (0, 0)))
    best_v = jnp.full((m_pad, k), np.inf, jnp.float32)
    best_i = jnp.full((m_pad, k), -1, jnp.int32)
    n = ds.base.shape[0]
    for a in range(0, n, chunk_rows):
        raw = ds.base[a:a + chunk_rows]
        if isinstance(raw, jax.Array):  # device-chunk provider
            xb = raw.astype(jnp.float32)
            if cos:
                xb = _norm(xb)
            if xb.shape[0] < chunk_rows:
                xb = jnp.pad(xb, ((0, chunk_rows - xb.shape[0]), (0, 0)),
                             constant_values=1e30)
        else:
            xbh = np.asarray(raw, np.float32)
            if cos:
                xbh = xbh / np.maximum(np.linalg.norm(
                    xbh, axis=1, keepdims=True), 1e-15)
            if xbh.shape[0] < chunk_rows:  # ragged tail: pad far away,
                xbh = np.pad(xbh, ((0, chunk_rows - xbh.shape[0]), (0, 0)),
                             constant_values=1e30)  # one compiled shape
            xb = jnp.asarray(xbh)
        best_v, best_i = merge_chunk(best_v, best_i, xb, jnp.int32(a))
    ds.groundtruth = np.asarray(jax.device_get(best_i))[:m]
    return ds


def recall(found_ids: np.ndarray, groundtruth: np.ndarray) -> float:
    """recall@k against groundtruth's first k columns (reference:
    data_export recall column) — delegates to stats.neighborhood_recall."""
    from ..stats.metrics import neighborhood_recall

    k = found_ids.shape[1]
    return float(neighborhood_recall(np.asarray(found_ids),
                                     np.asarray(groundtruth[:, :k])))
