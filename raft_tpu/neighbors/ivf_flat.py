"""IVF-Flat — inverted-file index over balanced-kmeans clusters.

TPU-native re-design of ``raft::neighbors::ivf_flat``
(ivf_flat-inl.cuh:65 build, :452 search; detail/ivf_flat_build.cuh;
detail/ivf_flat_search.cuh; interleaved scan kernel
detail/ivf_flat_interleaved_scan-inl.cuh). Design mapping:

- the reference stores raw vectors *interleaved in groups of 32*
  (kIndexGroupSize, ivf_flat_types.hpp:47) for coalesced warp scans. The
  TPU layout is **padded per-list blocks**: one dense ``[n_lists,
  max_list_size, dim]`` array (+ id array, -1 padded). Static shapes are
  what XLA needs, and balanced kmeans keeps the padding waste bounded —
  list-size balance is a first-class TPU concern (SURVEY.md §7 hard part c);
- the fused interleaved-scan + per-warp top-k kernel → coarse probe
  selection (Gram + select_k on the MXU), a batched gather of the probed
  list blocks, one batched matmul over candidates (``einsum`` on the MXU),
  and a fused select_k — XLA fuses the mask/epilogue into the contraction;
- query batching replaces the reference's stream-pool chunking: a
  ``lax.map`` over query tiles bounds the [tile, n_probes·list_size]
  intermediate.

Supported metrics: sqeuclidean / euclidean / inner_product / cosine
(float32 and int8/uint8 data — integers are scanned in int8 and
accumulated in int32 on the MXU, mirroring the reference's dp4a path).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core.errors import expects
from raft_tpu.core.tracing import annotate, traced, span
from raft_tpu.core import ids as _ids
from raft_tpu.core import serialize as ser
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
from raft_tpu.distance.types import DistanceType, resolve_metric
from raft_tpu.matrix import select_k as _select_k
from raft_tpu.obs import index_stats as _istats
from raft_tpu.robust import faults as _faults
from raft_tpu.utils.precision import get_precision

_SERIAL_VERSION = 1


@dataclasses.dataclass
class IndexParams:
    """reference: ``ivf_flat::index_params`` (ivf_flat_types.hpp)."""

    n_lists: int = 1024
    metric: str = "sqeuclidean"
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    add_data_on_build: bool = True
    # max_list_size = factor * n/n_lists, the rows past it dropped (or
    # spilled, below); None: no cap, as the reference's variable-length
    # lists — the padded capacity is the largest list, and every row is
    # kept in the list of its nearest center
    list_size_cap_factor: Optional[float] = 4.0
    # TPU-specific: cap padded capacity at the factor above and SPILL
    # overflow rows to their second-nearest list instead of dropping
    # them (ivf_common.spill_assignments) — every probe DMAs the padded
    # block, so skew-driven padding is wasted bandwidth on every scan;
    # spill with cap_factor ~1.5 shrinks the working set 2-3×
    spill: bool = False
    seed: int = 0


@dataclasses.dataclass
class SearchParams:
    """reference: ``ivf_flat::search_params`` (ivf_flat_types.hpp:157).

    ``scan_mode`` selects the TPU scan structure: "grouped" is the
    list-centric batch scan (see neighbors/ivf_common.py — each list block
    streams through the MXU once per query batch), "per_query" gathers
    each query's probed lists (lower latency for small batches), "auto"
    picks by batch size."""

    n_probes: int = 20
    query_tile: int = 256  # per_query path: bounds the per-step intermediate
    scan_mode: str = "auto"  # "auto" | "grouped" | "per_query"
    list_chunk: int = 64     # grouped path: segments scanned per step
    # per-segment candidate selection on the grouped path: "exact"
    # (lax.top_k / Pallas — the reference's semantics) or "approx"
    # (lax.approx_min_k, the TPU-hardware top-k: measured 30×+ cheaper
    # at scan shapes, making the scan matmul-bound; per-op recall is
    # targeted by scan_recall and end recall stays within ~1e-3 on
    # clustered data)
    scan_select: str = "exact"  # | "approx"
    scan_recall: float = 0.95   # approx select per-op recall target
    # refinement_rate pattern shared with ivf_pq (reference:
    # refine-inl.cuh): "f32_regen" scans k·refine_ratio candidates and
    # re-ranks exactly against search()'s ``dataset`` argument through
    # neighbors.refine's dispatch tier — recovers the recall the approx
    # hardware top-k trades away on oversampled configs
    refine: str = "none"  # | "f32_regen"
    refine_ratio: float = 2.0
    # host-resident re-rank bases (ISSUE 17): same knob as ivf_pq —
    # "auto" takes the tiered candidate-row prefetch pipeline when
    # eligible, "tiered" forces it, "serial" pins the serialized host
    # gather (the ladder's last-resort host_gather rung)
    refine_transfer: str = "auto"  # | "tiered" | "serial"


class IvfFlatIndex(flax.struct.PyTreeNode):
    """Padded-list IVF-Flat index (reference: ``ivf_flat::index``,
    ivf_flat_types.hpp:157-159 — TPU layout, see module docstring)."""

    centers: jax.Array       # [n_lists, dim] f32
    packed_data: jax.Array   # [n_lists, max_list_size, dim]
    packed_ids: jax.Array    # [n_lists, max_list_size] i32, -1 = pad
    packed_norms: jax.Array  # [n_lists, max_list_size] f32 squared norms
    list_sizes: jax.Array    # [n_lists] i32
    metric: str = flax.struct.field(pytree_node=False, default="sqeuclidean")

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def max_list_size(self) -> int:
        return self.packed_data.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_sizes))


def _normalize_rows(x):
    n = jnp.sqrt(jnp.maximum(jnp.sum(x * x, axis=-1, keepdims=True), 1e-12))
    return x / n


def _pack_lists(dataset: np.ndarray, labels: np.ndarray, n_lists: int,
                max_list_size: int, dtype) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side list packing (reference: detail/ivf_flat_build.cuh pack;
    build is host-orchestrated, like the reference's build pipeline).
    Fully vectorized: one argsort + fancy-indexed fill, no per-list loop."""
    n, d = dataset.shape
    order = np.argsort(labels, kind="stable")
    sorted_labels = labels[order]
    starts = np.searchsorted(sorted_labels, np.arange(n_lists))
    rank = np.arange(n) - starts[sorted_labels]   # slot within each list
    keep = rank < max_list_size
    dropped = int(n - keep.sum())
    packed = np.zeros((n_lists, max_list_size, d), dtype=dtype)
    # row ids are 0 … n−1: the table width follows the policy dtype of n
    # (core.ids) — int64 past 2³¹ rows
    ids = np.full((n_lists, max_list_size), -1, _ids.np_id_dtype(n))
    rows = order[keep]
    packed[sorted_labels[keep], rank[keep]] = dataset[rows]
    ids[sorted_labels[keep], rank[keep]] = rows
    sizes = np.minimum(np.bincount(labels, minlength=n_lists),
                       max_list_size).astype(np.int32)
    if dropped:
        from raft_tpu.core import logging as _log
        _log.warn("ivf_flat: dropped %d overflow vectors (raise "
                  "list_size_cap_factor)", dropped)
    return packed, ids, sizes


def _lane_round(size: int) -> int:
    """Round a list capacity up to a lane-friendly multiple — 128 for
    MXU-shaped scans once lists are that big, but only a multiple of 8
    below that so tiny-list indexes (actual max 15 → 16, not 128)
    aren't padded 8×."""
    size = max(8, size)
    if size >= 128:
        return -(-size // 128) * 128
    return -(-size // 8) * 8


def _fit_list_size(counts: np.ndarray, avg: int,
                   cap_factor: Optional[float]) -> int:
    """Padded list capacity: the actual max list size, clamped by the cap
    factor (``None``: not clamped), rounded up lane-friendly (see
    _lane_round). Sizing to the real histogram instead of the worst-case
    cap is a large scan-FLOP saver — padding is wasted work on every
    probe."""
    actual = int(counts.max()) if counts.size else 8
    if cap_factor is None:
        return _lane_round(actual)
    return _lane_round(min(max(8, int(avg * cap_factor)), actual))


@traced("raft_tpu.ivf_flat.build")
def build(dataset: jax.Array, params: Optional[IndexParams] = None) -> IvfFlatIndex:  # graftlint: disable-fn=GL01 (host-side histogram/pack by design)
    """Build the index (reference: ivf_flat::build, ivf_flat-inl.cuh:65):
    balanced-kmeans coarse fit on a trainset subsample, assign all rows,
    pack padded lists."""
    if params is None:
        params = IndexParams()
    mt = resolve_metric(params.metric)
    x = jnp.asarray(dataset)
    n, d = x.shape
    expects(params.n_lists <= n, "n_lists=%d > n=%d", params.n_lists, n)
    expects(not (params.spill and params.list_size_cap_factor is None),
            "spill=True needs a list_size_cap_factor to spill past "
            "(None keeps every row in its nearest list, uncapped)")

    spherical = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)
    km_params = KMeansBalancedParams(
        n_iters=params.kmeans_n_iters,
        metric="cosine" if spherical else "l2",
        seed=params.seed)

    # trainset subsample (reference: ivf_flat_build trainset_fraction)
    n_train = max(params.n_lists * 4, int(n * params.kmeans_trainset_fraction))
    n_train = min(n, n_train)
    if n_train < n:
        rng = np.random.default_rng(params.seed)
        train_rows = np.sort(rng.choice(n, n_train, replace=False))
        trainset = x[jnp.asarray(train_rows)]
    else:
        trainset = x
    with span("train") as _sp:
        centers = kmeans_balanced.fit(trainset.astype(jnp.float32),
                                      params.n_lists, km_params)
        _sp.attach(centers)
    del trainset  # wide datasets: the subsample copy is GBs

    avg = max(1, n // params.n_lists)

    if not params.add_data_on_build:
        # no cap: the least capacity, which extend() grows as it needs
        cap = params.list_size_cap_factor
        max_list_size = 8 if cap is None else max(8, int(avg * cap))
        packed = jnp.zeros((params.n_lists, max_list_size, d), x.dtype)
        ids = jnp.full((params.n_lists, max_list_size), -1, jnp.int32)
        sizes = jnp.zeros((params.n_lists,), jnp.int32)
        norms = jnp.zeros((params.n_lists, max_list_size), jnp.float32)
        return IvfFlatIndex(centers=centers, packed_data=packed,
                            packed_ids=ids, packed_norms=norms,
                            list_sizes=sizes, metric=mt.value)

    # assign + pack ON DEVICE (ivf_common.pack_lists, the same sort+
    # scatter the distributed build uses): the data never round-trips the
    # host, only the [n_lists] histogram does (it sizes the static padded
    # list capacity). The host packer remains for memmapped/chunked flows.
    from raft_tpu.neighbors import ivf_common as ic

    with span("assign") as _sp:
        if params.spill:
            # cap capacity at factor × mean and cascade overflow rows to
            # their next-nearest lists (see IndexParams.spill)
            lk = kmeans_balanced.predict_topk(centers,
                                              x.astype(jnp.float32),
                                              ic.SPILL_DEPTH, km_params)
            max_list_size = _lane_round(
                int(avg * params.list_size_cap_factor))
            labels = ic.spill_assignments(lk[:, 0], lk[:, 1],
                                          params.n_lists, max_list_size,
                                          *[lk[:, c] for c in
                                            range(2, lk.shape[1])])
            n_marker = int(jnp.sum(labels >= params.n_lists))
            if n_marker:
                # pack_lists' drop counter excludes out-of-range labels,
                # so double-overflow rows must be surfaced here
                from raft_tpu.core import logging as _log
                _log.warn("ivf_flat: %d rows overflowed every spill choice "
                          "at cap %d (raise list_size_cap_factor)",
                          n_marker, max_list_size)
                ic.note_rows_dropped("ivf_flat", n_marker)
        else:
            labels = kmeans_balanced.predict(centers, x.astype(jnp.float32),
                                             km_params)
            # histogram on host: the [n] labels transfer is small, and a
            # device scatter-add histogram serializes on TPU
            counts = np.bincount(np.asarray(labels),
                                 minlength=params.n_lists)
            max_list_size = _fit_list_size(counts, avg,
                                           params.list_size_cap_factor)
        _sp.attach(labels)
    with span("pack") as _sp:
        if (n + params.n_lists * max_list_size) * d * x.dtype.itemsize \
                > (8 << 30):
            # wide datasets: the one-shot pack's gather copy OOMs (see
            # pack_rows_chunked)
            packed, ids, sizes, dropped = ic.pack_rows_chunked(
                x, labels, params.n_lists, max_list_size,
                chunk_rows=1 << 16)
        else:
            (packed,), ids, sizes, dropped, _ = ic.pack_lists_jit(
                [x], labels, _ids.make_ids(n),
                n_lists=params.n_lists, L=max_list_size,
                fill_values=[jnp.zeros((), x.dtype)])
        _sp.attach(packed, ids)
    n_drop = int(dropped)
    if n_drop:
        from raft_tpu.core import logging as _log
        _log.warn("ivf_flat: dropped %d overflow vectors (raise "
                  "list_size_cap_factor%s)", n_drop,
                  "" if params.spill else " or set spill=True")
        ic.note_rows_dropped("ivf_flat", n_drop)
    norms = jnp.sum(packed.astype(jnp.float32) ** 2, axis=-1)
    index = IvfFlatIndex(centers=centers, packed_data=packed,
                         packed_ids=ids, packed_norms=norms,
                         list_sizes=sizes, metric=mt.value)
    ic.note_list_fill("ivf_flat", sizes, max_list_size)
    _istats.note_index_stats(index, name="ivf_flat.build", cheap=True)
    return index


@traced("raft_tpu.ivf_flat.build_distributed")
def build_distributed(dataset, params: Optional[IndexParams] = None, *,
                      mesh, axis: str = "shard",
                      chunk_rows: int = 1 << 18,
                      max_train_rows: int = 1 << 21,
                      prefetch: bool = True,
                      coarse: str = "replicated",
                      progress: bool = False):
    """Distributed chunked build from a host array/memmap — the
    IVF-Flat twin of :func:`raft_tpu.neighbors.ivf_pq.build_distributed`
    (see it and :mod:`raft_tpu.parallel.build` for the shard/prefetch/
    comms structure). Returns a ``parallel.ivf.ShardedIvfFlat`` the
    sharded searcher consumes directly;
    ``parallel.build.assemble_ivf_flat`` of the default
    (``coarse="replicated"``) result is bit-identical to
    :func:`build` over the same dataset/params while the trainset stays
    under ``max_train_rows``."""
    if params is None:
        params = IndexParams()
    from raft_tpu.parallel import build as _dbuild

    return _dbuild.build_ivf_flat_distributed(
        dataset, params, mesh, axis=axis, chunk_rows=chunk_rows,
        max_train_rows=max_train_rows, prefetch=prefetch, coarse=coarse,
        progress=progress)


@traced("raft_tpu.ivf_flat.extend")
def extend(index: IvfFlatIndex, new_vectors: jax.Array,  # graftlint: disable-fn=GL01 (host re-pack by design)
           new_ids: Optional[jax.Array] = None) -> IvfFlatIndex:
    """Append vectors (reference: ivf_flat::extend). Host-side re-pack with
    capacity growth; centers unchanged."""
    mt = resolve_metric(index.metric)
    spherical = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)
    km_params = KMeansBalancedParams(metric="cosine" if spherical else "l2")

    old_n = index.size
    new_vectors = jnp.asarray(new_vectors)
    if new_ids is None:
        new_ids = _ids.make_ids(new_vectors.shape[0], start=old_n)
    labels = np.asarray(kmeans_balanced.predict(
        index.centers, new_vectors.astype(jnp.float32), km_params))

    # host re-pack: merge existing rows with new ones
    n_lists, L, d = index.packed_data.shape
    old_sizes = np.asarray(index.list_sizes)
    new_counts = np.bincount(labels, minlength=n_lists)
    need = old_sizes + new_counts
    new_L = max(L, int(need.max()))
    new_L = max(8, -(-new_L // 8) * 8)

    old_ids = np.asarray(index.packed_ids)
    ni = np.asarray(new_ids)
    packed = np.zeros((n_lists, new_L, d), np.asarray(index.packed_data).dtype)
    ids = np.full((n_lists, new_L), -1, _ids.np_id_dtype_like(old_ids, ni))
    packed[:, :L] = np.asarray(index.packed_data)
    ids[:, :L] = old_ids
    nv = np.asarray(new_vectors)
    # vectorized append: slot = old_size[list] + rank within the new rows
    order = np.argsort(labels, kind="stable")
    sorted_l = labels[order]
    starts = np.searchsorted(sorted_l, np.arange(n_lists))
    rank = np.arange(len(labels)) - starts[sorted_l]
    slot = old_sizes[sorted_l] + rank
    keep = slot < new_L
    packed[sorted_l[keep], slot[keep]] = nv[order[keep]]
    ids[sorted_l[keep], slot[keep]] = ni[order[keep]]
    fill = np.minimum(need, new_L)
    packed_j = jnp.asarray(packed)
    out = IvfFlatIndex(
        centers=index.centers, packed_data=packed_j, packed_ids=jnp.asarray(ids),
        packed_norms=jnp.sum(packed_j.astype(jnp.float32) ** 2, axis=-1),
        list_sizes=jnp.asarray(fill.astype(np.int32)), metric=index.metric)
    _istats.note_index_stats(out, name="ivf_flat.extend", cheap=True)
    return out


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

# Stage scopes of the search programs (op_name metadata only): the whole
# program is the scan of the probed lists with its merge, and probe
# selection inside it is its own stage, as is the per-query path's
# gather of the probed list blocks.
_COARSE = "raft_tpu.ivf_flat.coarse_quantize"
_GATHER = "raft_tpu.ivf_flat.gather"
_SCAN = "raft_tpu.ivf_flat.scan"


def _coarse_distances(q, centers, mt):
    """Query→center scores for probe selection (reference:
    detail/ivf_flat_search.cuh select_clusters gemm)."""
    g = lax.dot_general(q, centers, (((1,), (1,)), ((), ())),
                        precision=get_precision(),
                        preferred_element_type=jnp.float32)
    if mt == DistanceType.InnerProduct:
        return g, False
    if mt == DistanceType.CosineExpanded:
        qn = jnp.sqrt(jnp.maximum(jnp.sum(q * q, 1), 1e-30))
        cn = jnp.sqrt(jnp.maximum(jnp.sum(centers * centers, 1), 1e-30))
        return 1.0 - g / (qn[:, None] * cn[None, :]), True
    c_sq = jnp.sum(centers * centers, axis=1)
    q_sq = jnp.sum(q * q, axis=1)
    return jnp.maximum(q_sq[:, None] + c_sq[None, :] - 2.0 * g, 0.0), True


@partial(jax.jit, static_argnames=("k", "n_probes", "query_tile"))
@annotate(_SCAN)
def _search_impl(index: IvfFlatIndex, queries: jax.Array, k: int,
                 n_probes: int, query_tile: int, filter_bits=None):
    mt = resolve_metric(index.metric)
    q_all = queries.astype(jnp.float32)
    m = q_all.shape[0]
    L = index.max_list_size
    sqrt_out = mt == DistanceType.L2SqrtExpanded
    select_min = mt != DistanceType.InnerProduct

    with annotate(_COARSE):
        coarse, coarse_min = _coarse_distances(q_all, index.centers, mt)
        _, probes = _select_k(coarse, n_probes, select_min=coarse_min)

    def search_tile(args):
        q, probe = args  # [t, dim], [t, P]
        t = q.shape[0]
        with annotate(_GATHER):
            cand_data = index.packed_data[probe].astype(jnp.float32)
            cand_ids = index.packed_ids[probe].reshape(t, n_probes * L)
            cand_norms = index.packed_norms[probe].reshape(t, n_probes * L)
        cand = cand_data.reshape(t, n_probes * L, index.dim)
        scores = jnp.einsum("td,tcd->tc", q, cand,
                            precision=get_precision(),
                            preferred_element_type=jnp.float32)
        if mt == DistanceType.InnerProduct:
            dists = scores
            invalid_val = -jnp.inf
        elif mt == DistanceType.CosineExpanded:
            qn = jnp.sqrt(jnp.maximum(jnp.sum(q * q, 1), 1e-30))
            cn = jnp.sqrt(jnp.maximum(cand_norms, 1e-30))
            dists = 1.0 - scores / (qn[:, None] * cn)
            invalid_val = jnp.inf
        else:
            c_sq = cand_norms
            q_sq = jnp.sum(q * q, axis=1)
            dists = jnp.maximum(q_sq[:, None] + c_sq - 2.0 * scores, 0.0)
            if sqrt_out:
                dists = jnp.sqrt(dists)
            invalid_val = jnp.inf
        valid = cand_ids >= 0
        if filter_bits is not None:
            from raft_tpu.neighbors.sample_filter import passes

            valid = passes(filter_bits, cand_ids)
        dists = jnp.where(valid, dists, invalid_val)
        vals, pos = _select_k(dists, k, select_min=select_min)
        ids = jnp.take_along_axis(cand_ids, pos, axis=1)
        return vals, ids

    if m <= query_tile:
        return search_tile((q_all, probes))

    n_tiles = -(-m // query_tile)
    pad = n_tiles * query_tile - m
    qp = jnp.pad(q_all, ((0, pad), (0, 0)))
    pp = jnp.pad(probes, ((0, pad), (0, 0)))
    vals, ids = lax.map(
        search_tile,
        (qp.reshape(n_tiles, query_tile, -1), pp.reshape(n_tiles, query_tile, -1)))
    return (vals.reshape(n_tiles * query_tile, k)[:m],
            ids.reshape(n_tiles * query_tile, k)[:m])


@partial(jax.jit, static_argnames=("k", "n_probes", "seg", "n_seg",
                                   "seg_chunk", "use_pallas", "select_impl",
                                   "select_recall", "use_segk"))
@annotate(_SCAN)
def _search_grouped(index: IvfFlatIndex, queries: jax.Array, k: int,
                    n_probes: int, seg: int, n_seg: int, seg_chunk: int,
                    use_pallas: bool = False, filter_bits=None,
                    select_impl: str = "exact",
                    select_recall: float = 0.95,
                    use_segk: bool = False):
    """Segmented list-centric batch scan (see ivf_common module
    docstring): probe selection, probe segmenting, the MXU scan over
    segment chunks, and the final merge — ONE jitted program, statically
    shaped by (B, n_probes, n_lists, seg). TPU counterpart of the
    reference's interleaved scan (ivf_flat_interleaved_scan-inl.cuh)
    with the loop order inverted. ``use_pallas`` (static) routes the
    per-chunk scan to the fused Pallas kernel."""
    from raft_tpu.neighbors import ivf_common as ic

    mt = resolve_metric(index.metric)
    q_all = queries.astype(jnp.float32)
    B = q_all.shape[0]
    n_lists, L, d = index.packed_data.shape
    sqrt_out = mt == DistanceType.L2SqrtExpanded
    ip = mt == DistanceType.InnerProduct
    cos = mt == DistanceType.CosineExpanded
    select_min = not ip
    invalid = -jnp.inf if ip else jnp.inf

    with annotate(_COARSE):
        coarse, coarse_min = _coarse_distances(q_all, index.centers, mt)
        _, probes = _select_k(coarse, n_probes, select_min=coarse_min)
    seg_list, seg_q, pair_seg, pair_slot = ic.segment_probes(
        probes, n_lists, seg, n_seg)

    q_sq = jnp.sum(q_all * q_all, axis=1)                 # [B]
    qn = jnp.sqrt(jnp.maximum(q_sq, 1e-30))

    kk_ = min(k, L)
    if use_segk:
        # scalar-prefetch kernel: list blocks DMA'd from the full packed
        # array at copy bandwidth (the XLA gather of the same blocks
        # measured ~20 GB/s and dominated the scan); per-tile-min
        # selection merged with one tiny top-k
        from raft_tpu.ops import pallas_kernels as _pk

        met = "ip" if ip else ("cos" if cos else "l2")
        qv_all = q_all[jnp.clip(seg_q, 0, B - 1)]         # [n_seg, S, d]
        keys, kids = _pk.segmented_scan_topk(
            seg_list, qv_all, index.packed_data, index.packed_ids, met,
            interpret=not _pk._on_tpu())
        out_vals, out_ids = ic.merge_bin_results(
            keys, kids, pair_seg, pair_slot, k, select_min, invalid,
            select_recall)
        if sqrt_out:
            out_vals = jnp.sqrt(out_vals)
        return out_vals, out_ids

    C = seg_chunk
    n_chunks = -(-n_seg // C)
    nsp = n_chunks * C
    seg_list = jnp.pad(seg_list, (0, nsp - n_seg))
    seg_q = jnp.pad(seg_q, ((0, nsp - n_seg), (0, 0)), constant_values=-1)

    from raft_tpu.ops import pallas_kernels as _pk

    kk = min(k, L)  # a single list holds at most L candidates

    def scan_chunk(args):
        sl, qt = args                                     # [C], [C, seg]
        data = index.packed_data[sl].astype(jnp.float32)  # [C, L, d]
        lids = index.packed_ids[sl]
        # the scan is HBM-gather-bound (XLA TPU gathers run ~20 GB/s vs
        # 800+ streaming, measured): derive validity from the gathered
        # ids and recompute norms from the gathered data instead of
        # gathering two more [C, L] arrays
        valid = lids >= 0
        if filter_bits is not None:
            from raft_tpu.neighbors.sample_filter import passes

            valid &= passes(filter_bits, lids)
        norms = jnp.sum(data * data, axis=-1)             # [C, L]
        qi = jnp.clip(qt, 0, B - 1)                       # [C, seg]
        qv = q_all[qi]                                    # [C, seg, d]
        # pad slots (qt == -1) compute against query 0 and are simply
        # never gathered back — masking them would cost more than the
        # wasted lanes
        if use_pallas:
            # fused contraction + epilogue + local top-k in VMEM — the
            # [C·seg, L] distance block never reaches HBM (reference:
            # the fused scan kernels, ivf_flat_interleaved_scan-inl.cuh)
            met = "ip" if ip else ("cos" if cos else "l2")
            mask_add = jnp.where(valid, 0.0, jnp.inf)
            keys, pos = _pk.grouped_scan_topk(
                qv, data, mask_add, kk, met, bq=seg,
                interpret=not _pk._on_tpu())
            vals = -keys if ip else keys
            vals = jnp.where(pos < 0, invalid, vals)
            cids = jax.vmap(lambda l, p: l[jnp.clip(p, 0, L - 1)])(lids, pos)
            cids = jnp.where(pos < 0, -1, cids)
            return vals, cids
        scores = jnp.einsum("gqd,gld->gql", qv, data,
                            precision=get_precision(),
                            preferred_element_type=jnp.float32)
        if ip:
            dists = scores
        elif cos:
            cn = jnp.sqrt(jnp.maximum(norms, 1e-30))
            dists = 1.0 - scores / (qn[qi][:, :, None] * cn[:, None, :])
        else:
            dists = jnp.maximum(
                q_sq[qi][:, :, None] + norms[:, None, :] - 2.0 * scores, 0.0)
        dists = jnp.where(valid[:, None, :], dists, invalid)
        if select_impl == "approx":
            # hardware top-k (TPU approx reduction): per-op recall
            # targeted, 30×+ cheaper than the sort-based exact select
            if select_min:
                vals, pos = lax.approx_min_k(
                    dists.reshape(C * seg, L), kk,
                    recall_target=select_recall)
            else:
                vals, pos = lax.approx_max_k(
                    dists.reshape(C * seg, L), kk,
                    recall_target=select_recall)
        else:
            vals, pos = _select_k(dists.reshape(C * seg, L), kk,
                                  select_min=select_min)
        vals = vals.reshape(C, seg, kk)
        pos = pos.reshape(C, seg, kk)
        cids = jax.vmap(lambda l, p: l[p])(lids, pos)     # [C, seg, kk]
        cids = jnp.where(vals == invalid, -1, cids)       # filtered/padded
        return vals, cids

    vals, cids = lax.map(
        scan_chunk, (seg_list.reshape(n_chunks, C),
                     seg_q.reshape(n_chunks, C, seg)))
    vals = vals.reshape(nsp, seg, kk)
    cids = cids.reshape(nsp, seg, kk)

    pv, pi = ic.gather_segment_results(vals, cids, pair_seg, pair_slot)
    out_vals, out_ids = _select_k(pv.reshape(B, n_probes * kk),
                                  min(k, n_probes * kk),
                                  select_min=select_min,
                                  input_indices=pi.reshape(B, n_probes * kk))
    if k > n_probes * kk:  # fewer candidates than asked: pad with invalid
        pad = k - n_probes * kk
        out_vals = jnp.pad(out_vals, ((0, 0), (0, pad)),
                           constant_values=invalid)
        out_ids = jnp.pad(out_ids, ((0, 0), (0, pad)), constant_values=-1)
    if sqrt_out:
        out_vals = jnp.sqrt(out_vals)
    return out_vals, out_ids


def _route_refined(index: IvfFlatIndex, queries: jax.Array, k: int,
                   params: SearchParams, filter_bitset, dataset
                   ) -> Tuple[jax.Array, jax.Array]:
    """``refine="f32_regen"``: oversampled scan + exact re-rank through
    neighbors.refine's dispatch tier (fused Pallas gather-refine on TPU
    oversampled shapes / XLA einsum / host gather) — same routing as
    ivf_pq's refined path."""
    import dataclasses

    from raft_tpu.neighbors import refine as _refine

    expects(params.refine == "f32_regen",
            "unknown refine mode %r (supported: 'none', 'f32_regen')",
            params.refine)
    expects(dataset is not None,
            "refine='f32_regen' needs search(..., dataset=...): the "
            "exact rows to re-rank against")
    dshape = getattr(dataset, "shape", None)
    expects(dshape is not None and len(dshape) == 2
            and dshape[1] == index.dim,
            "refine dataset shape %s does not match the index dim %d",
            tuple(dshape) if dshape else None, index.dim)
    expects(params.refine_ratio >= 1.0,
            "refine_ratio must be >= 1 (got %s)", params.refine_ratio)
    k_cand = max(k, int(round(k * params.refine_ratio)))
    scan_params = dataclasses.replace(params, refine="none")
    # host-resident base → the memory tier (ISSUE 17): decided BEFORE
    # the scan, same routing as ivf_pq's refined path
    if (not isinstance(dataset, jax.Array)
            and not hasattr(dataset, "_block")):
        from raft_tpu.neighbors import tiered as _tiered

        if _tiered.tiered_refine_wanted(dataset, queries.shape[0],
                                        k_cand, index.dim, params):
            return _tiered.search_refined_tiered(
                search, index, queries, k, k_cand, scan_params,
                filter_bitset, dataset, index.metric)
    _, i0 = search(index, queries, k_cand, scan_params, filter_bitset)
    if hasattr(dataset, "_block") and hasattr(dataset, "chunk_rows"):
        return _refine.refine_provider(dataset, queries, i0, k,
                                       metric=index.metric)
    if isinstance(dataset, jax.Array):
        # i0 is already filter-clean; the refine-tier filter is defense
        # in depth (the fused kernel's in-DMA bit test costs nothing)
        return _refine.refine(dataset, queries, i0, k, metric=index.metric,
                              filter_bits=filter_bitset)
    return _refine.refine_gathered(dataset, queries, i0, k,
                                   metric=index.metric)


@traced("raft_tpu.ivf_flat.search")
def search(index, queries: jax.Array, k: int,
           params: Optional[SearchParams] = None,
           filter_bitset: Optional[jax.Array] = None,
           dataset=None, *, mesh=None,
           mesh_axis: str = "shard",
           merge: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Search the index (reference: ivf_flat::search, ivf_flat-inl.cuh:452;
    filtered overload ivf_flat-inl.cuh search_with_filtering).

    Returns (distances [m, k], ids [m, k]); ids are dataset row numbers,
    -1 marks slots beyond the number of valid candidates.
    ``filter_bitset``: optional packed bitset over dataset rows (see
    neighbors.sample_filter) — cleared bits are excluded.
    ``params.refine="f32_regen"`` + ``dataset`` re-ranks an oversampled
    scan exactly (see SearchParams.refine).

    **Pod-scale dispatch**: handed a ``parallel.ShardedIvfFlat`` (plus
    its ``mesh``), routes to the sharded search tier with the
    cross-shard merge picked by ``merge`` (auto | allgather | ring, see
    ``parallel.merge``)."""
    if params is None:
        params = SearchParams()
    from raft_tpu.neighbors import ivf_common as ic

    _divf = ic.sharded_dispatch(index, mesh, "ShardedIvfFlat")
    if _divf is not None:
        expects(params.refine == "none",
                "sharded IVF-Flat search does not support refine yet")
        return _divf.search_ivf_flat(params, index, queries, k, mesh,
                                     axis=mesh_axis, merge=merge,
                                     filter_bitset=filter_bitset)
    expects(queries.ndim == 2 and queries.shape[1] == index.dim,
            "queries must be [m, %d]", index.dim)
    _faults.faultpoint("ivf_flat.search")
    if params.refine != "none":
        return _route_refined(index, queries, k, params, filter_bitset,
                              dataset)
    program, args, kwargs = _plan(index, queries, k, params, filter_bitset)
    return program(*args, **kwargs)


def _plan(index: IvfFlatIndex, queries: jax.Array, k: int,
          params: SearchParams, filter_bitset=None):
    """The single-chip search program for this call and what it is
    called with: ``(program, args, kwargs)``, the program one of the
    jitted :func:`_search_impl` (per query) and :func:`_search_grouped`
    (grouped). ``program.lower(*args, **kwargs)`` gives the executable
    a call of :func:`search` runs."""
    n_probes = min(params.n_probes, index.n_lists)
    B = queries.shape[0]
    mode = params.scan_mode
    if mode == "auto":
        # grouped wins once the batch populates the per-list queues
        mode = ("grouped" if B * n_probes >= 2 * index.n_lists
                else "per_query")
    if mode == "grouped":
        from raft_tpu.neighbors import ivf_common as ic

        # segmented scan: the table shape is a function of (B, n_probes,
        # n_lists, seg) alone — no probe histogram, no host sync, one
        # jitted program per static config (see ivf_common docstring)
        seg = ic.SEGMENT_SIZE
        pairs = B * n_probes
        n_seg = ic.n_segments(pairs, index.n_lists, seg)
        L = index.max_list_size
        kk = min(k, L)
        if params.scan_mode == "grouped" or ic.grouped_mem_ok(
                n_seg, seg, kk, pairs):
            chunk = ic.fit_seg_chunk(seg, L, index.dim, params.list_chunk)
            from raft_tpu.ops import pallas_kernels as _pk

            approx = params.scan_select == "approx"
            segk = (approx and filter_bitset is None
                    and _pk.pallas_segmented_wanted(kk, L, index.dim,
                                                    S=seg))
            wants = (not approx) and _pk.pallas_grouped_wanted(
                kk, L, index.dim, bq=seg)
            return _search_grouped, (index, queries, k, n_probes, seg,
                                     n_seg, chunk), dict(
                use_pallas=wants, filter_bits=filter_bitset,
                select_impl=params.scan_select,
                select_recall=params.scan_recall, use_segk=segk)
    return _search_impl, (index, queries, k, n_probes, _fit_query_tile(
        params.query_tile, n_probes, index)), dict(filter_bits=filter_bitset)


@traced("raft_tpu.ivf_flat.search_resilient")
def search_resilient(index: IvfFlatIndex, queries: jax.Array, k: int,
                     params: Optional[SearchParams] = None,
                     filter_bitset: Optional[jax.Array] = None,
                     dataset=None,
                     deadline=None) -> Tuple[jax.Array, jax.Array]:
    """:func:`search` behind the standard degradation ladder
    (:mod:`raft_tpu.robust.degrade`, same wiring as
    ``ivf_pq.search_resilient`` minus the LUT rung — IVF-Flat has no
    LUT to quantize): RESOURCE_EXHAUSTED walks halve-batch → decline
    fused tier → host gather (then keeps halving), counted in
    ``degrade.steps{site=ivf_flat.search,...}``. ``deadline`` (a
    :class:`raft_tpu.robust.retry.Deadline`) is the request's shared
    wall-clock budget — the ladder aborts with ``DeadlineExceeded``
    instead of retrying past it (same contract as
    ``ivf_pq.search_resilient``)."""
    from raft_tpu.robust import degrade as _dg

    if params is None:
        params = SearchParams()
    queries = jnp.asarray(queries)
    return _dg.run_with_degradation(
        _dg.batched_search_call(search, index, queries, k, filter_bitset,
                                deadline=deadline, site="ivf_flat.search"),
        {"params": params, "dataset": dataset},
        _dg.standard_search_ladder(queries.shape[0], has_lut=False),
        site="ivf_flat.search", deadline=deadline)


def _fit_query_tile(want: int, n_probes: int, index: IvfFlatIndex) -> int:
    """Largest per_query tile ≤ ``want`` whose [t, n_probes, L, d] f32
    candidate gather stays under ~1 GB — at 1M rows (L≈4k) the default
    256-query tile would gather 17 GB and OOM the chip."""
    L, d = index.max_list_size, index.dim
    return max(1, min(want, (1 << 30) // max(1, n_probes * L * d * 4)))


# ---------------------------------------------------------------------------
# serialization (reference: neighbors/ivf_flat_serialize.cuh)
# ---------------------------------------------------------------------------

def save(index: IvfFlatIndex, path: str) -> None:
    ser.save_arrays(path, "ivf_flat", _SERIAL_VERSION,
                    {"metric": index.metric},
                    {"centers": index.centers,
                     "packed_data": index.packed_data,
                     "packed_ids": index.packed_ids,
                     "packed_norms": index.packed_norms,
                     "list_sizes": index.list_sizes})


def load(path: str) -> IvfFlatIndex:
    version, meta, arrays = ser.load_arrays(path, "ivf_flat")
    expects(version == _SERIAL_VERSION, "unsupported ivf_flat version %d", version)
    return IvfFlatIndex(
        centers=jnp.asarray(arrays["centers"]),
        packed_data=jnp.asarray(arrays["packed_data"]),
        packed_ids=jnp.asarray(arrays["packed_ids"]),
        packed_norms=jnp.asarray(arrays["packed_norms"]),
        list_sizes=jnp.asarray(arrays["list_sizes"]),
        metric=meta["metric"])
