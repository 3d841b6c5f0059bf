"""Brute-force exact kNN — tiled over query×index to bound memory.

TPU-native counterpart of ``raft::neighbors::brute_force``
(neighbors/brute_force-inl.cuh:156 ``knn``; detail/knn_brute_force.cuh:58
``tiled_brute_force_knn``, :320 ``brute_force_knn_impl``; index type with
cached norms brute_force_types.hpp). Design mapping:

- the reference's stream-pool parallelism over index chunks → one fused XLA
  program: per-tile Gram matmul (MXU) + per-tile ``select_k`` + cross-tile
  merge ``select_k``, scheduled by XLA;
- the fused-L2 small-D fast path (fused_l2_knn-inl.cuh) → same scan-fused
  shape, since XLA fuses distance epilogue into the matmul tile;
- distributed (sharded-index) search lives in raft_tpu.parallel and merges
  per-shard results with :func:`raft_tpu.matrix.merge_parts`.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.errors import expects
from raft_tpu.core.tracing import annotate, traced
from raft_tpu.core import ids as _ids
from raft_tpu.distance import pairwise_distance, resolve_metric, DistanceType, SELECT_MIN
from raft_tpu.matrix import select_k as _select_k
from raft_tpu.matrix.select_k import merge_parts
from raft_tpu.utils.precision import get_precision

# Max elements of one [query_tile, index_tile] distance block (~256 MB f32).
_TILE_BUDGET_ELEMS = 1 << 26

# Strided-bin width of the per-tile candidate cut (lane-shaped).
_BIN_LANES = 128

# Stage scopes of the search program (op_name metadata only): the
# distance contraction with its norms, the per-tile candidate cut, the
# running merge, and the per-call layout work around them. The
# single-block select keeps select_k's own scope.
_DISTANCE = "raft_tpu.brute_force.distance"
_TILE_CUT = "raft_tpu.brute_force.tile_cut"
_MERGE = "raft_tpu.brute_force.merge"
_PREPARE = "raft_tpu.brute_force.prepare"


def _two_best_per_bin(dists: jax.Array, select_min: bool):
    """Per-tile candidate cut: the two best entries of each of 128
    STRIDED bins (position mod 128) with their in-tile positions —
    [m, it] → ([m, 256], [m, 256]) in two vectorized min/argmin passes.
    The same reduction the segmented IVF kernel applies in VMEM
    (ops/pallas_kernels._segmented_scan_kernel), here in XLA for the
    brute-force tile scan: it replaces a k-round extraction select with
    work the VPU does in one sweep, and positions come from arithmetic
    (argmin·128 + lane), never a gather."""
    m, it = dists.shape
    s = dists if select_min else -dists
    T = it // _BIN_LANES
    d3 = s.reshape(m, T, _BIN_LANES)
    lane = jnp.arange(_BIN_LANES, dtype=jnp.int32)[None, :]
    mn1 = jnp.min(d3, axis=1)
    a1 = jnp.argmin(d3, axis=1).astype(jnp.int32)
    t_iota = jax.lax.broadcasted_iota(jnp.int32, d3.shape, 1)
    d3b = jnp.where(t_iota == a1[:, None, :], jnp.inf, d3)
    mn2 = jnp.min(d3b, axis=1)
    a2 = jnp.argmin(d3b, axis=1).astype(jnp.int32)
    vals = jnp.concatenate([mn1, mn2], axis=1)
    pos = jnp.concatenate([a1 * _BIN_LANES + lane,
                           a2 * _BIN_LANES + lane], axis=1)
    if not select_min:
        vals = jnp.where(jnp.isinf(vals), -jnp.inf, -vals)
    return vals, pos


def _top_k_merge(cat_v: jax.Array, k: int, select_min: bool):
    """Small exact top-k over the [m, k+256] merge row (lax.top_k —
    narrow rows, where the sort-based select is already optimal)."""
    if select_min:
        nv, pos = lax.top_k(-cat_v, k)
        return -nv, pos
    return lax.top_k(cat_v, k)


class BruteForceIndex(flax.struct.PyTreeNode):
    """Brute-force index: the dataset plus cached norms
    (reference: brute_force_types.hpp ``brute_force::index``).

    A pytree (arrays are leaves, metric config is static) so whole
    searches jit over it — the search path must be ONE compiled program:
    op-by-op dispatch pays a host dispatch per op."""

    dataset: jax.Array          # [n, d]
    norms: Optional[jax.Array]  # [n] cached squared L2 norms (L2/cosine only)
    metric: DistanceType = flax.struct.field(pytree_node=False,
                                             default=DistanceType.L2Expanded)
    metric_arg: float = flax.struct.field(pytree_node=False, default=2.0)

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]


@traced("raft_tpu.brute_force.build")
def build(dataset: jax.Array, metric="euclidean", metric_arg: float = 2.0) -> BruteForceIndex:
    """Build a brute-force index (reference: brute_force::build).

    Caches squared norms for expanded metrics so repeated searches skip
    recomputing them (brute_force_types.hpp norms caching).
    """
    mt = resolve_metric(metric)
    norms = None
    if mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
              DistanceType.CosineExpanded):
        ds = dataset.astype(jnp.float32)
        norms = jnp.sum(ds * ds, axis=1)
    return BruteForceIndex(dataset=dataset, norms=norms, metric=mt, metric_arg=metric_arg)


def _choose_tiles(m: int, n: int, d: int) -> Tuple[int, int]:
    """Tile-size heuristic (reference: knn_brute_force.cuh:80): bound the
    [qt, it] distance block; favor wide index tiles (longer MXU contractions
    per select)."""
    if m * n <= _TILE_BUDGET_ELEMS:
        return m, n
    it = min(n, max(1 << 14, _TILE_BUDGET_ELEMS // max(m, 1)))
    qt = max(1, _TILE_BUDGET_ELEMS // it)
    return min(m, qt), it


def _expanded_block(q, db, q_sq, db_sq, metric):
    g = lax.dot_general(q, db, (((1,), (1,)), ((), ())),
                        precision=get_precision(),
                        preferred_element_type=jnp.float32)
    if metric == DistanceType.InnerProduct:
        return g
    if metric == DistanceType.CosineExpanded:
        nq = jnp.sqrt(jnp.maximum(q_sq, 1e-30))
        nd = jnp.sqrt(jnp.maximum(db_sq, 1e-30))
        return 1.0 - g / (nq[:, None] * nd[None, :])
    d2 = jnp.maximum(q_sq[:, None] + db_sq[None, :] - 2.0 * g, 0.0)
    if metric == DistanceType.L2SqrtExpanded:
        return jnp.sqrt(d2)
    return d2


# traced OUTSIDE jit: the named_scope still labels ops (the first call
# traces inside the wrapper's context), and the wrapper now runs per
# call — so the obs span records every search, not just the trace
@traced("raft_tpu.brute_force.knn")
@partial(jax.jit, static_argnames=("k", "impl"))
def knn(
    index: BruteForceIndex,
    queries: jax.Array,
    k: int,
    filter_bitset: Optional[jax.Array] = None,
    impl: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Exact k nearest neighbors (reference: brute_force::knn,
    brute_force-inl.cuh:156). Returns (distances [m,k], indices [m,k]).
    The whole search is one jitted program (index is a pytree).

    ``filter_bitset``: optional packed bitset over index rows (see
    neighbors.sample_filter) — cleared bits are excluded from results.
    ``impl``: "auto" uses the strided-bin tile cut (exact up to a
    ~2e-6/query bin-collision chance, see _two_best_per_bin); "sort"
    forces the guaranteed-exact per-tile selection."""
    expects(queries.ndim == 2, "queries must be [m, d]")
    expects(queries.shape[1] == index.dim, "query dim %d != index dim %d",
            queries.shape[1], index.dim)
    m, d = queries.shape
    n = index.size
    expects(k <= n, "k=%d > index size %d", k, n)
    mt = index.metric
    select_min = SELECT_MIN[mt]

    fast = mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                  DistanceType.CosineExpanded, DistanceType.InnerProduct)

    qt, it = _choose_tiles(m, n, d)

    # optional pre-filter mask over index rows (cleared bit → excluded)
    fmask = None
    if filter_bitset is not None:
        from raft_tpu.core import bitset as _bitset

        with annotate(_PREPARE):
            fmask = _bitset.to_mask(filter_bitset, n)

    def _finalize(vals, ids):
        """With a filter, fewer than k candidates may survive: the inf
        slots would otherwise carry arbitrary ids — mark them -1 (the
        same pad convention the IVF searches use)."""
        if fmask is None:
            return vals, ids
        with annotate(_PREPARE):
            bad = jnp.isinf(vals)
            return vals, jnp.where(bad, -1, ids)

    if fast:
        with annotate(_DISTANCE):
            q = queries.astype(jnp.float32)
            q_sq = jnp.sum(q * q, axis=1)
            db = index.dataset.astype(jnp.float32)
            db_sq = (index.norms if index.norms is not None
                     else jnp.sum(db * db, axis=1))

        if it >= n:
            with annotate(_DISTANCE):
                dists = _expanded_block(q, db, q_sq, db_sq, mt)
                if fmask is not None:
                    dists = jnp.where(fmask[None, :], dists,
                                      jnp.inf if select_min else -jnp.inf)
            return _finalize(*_select_k(dists, k, select_min=select_min))

        # scan over index tiles with a running top-k merge — never holds the
        # full [m, n] matrix (tiled_brute_force_knn:234-276).
        n_tiles = -(-n // it)
        pad = n_tiles * it - n
        pad_val = jnp.inf if select_min else -jnp.inf
        with annotate(_PREPARE):
            dbp = jnp.pad(db, ((0, pad), (0, 0)))
            dbp_sq = jnp.pad(db_sq, (0, pad), constant_values=pad_val)
            db_blocks = dbp.reshape(n_tiles, it, d)
            sq_blocks = dbp_sq.reshape(n_tiles, it)
        kk = min(k, it)
        # the depth-2 strided-bin cut needs k ≤ 2·bins per tile and a
        # lane-aligned tile; it replaces a per-tile k-extraction select
        # whose running-buffer loop measured ~11 ms per [10K, 16K] tile
        # (select dominated the whole scan: 13.7K q/s end to end)
        use_bins = (impl != "sort" and it % _BIN_LANES == 0
                    and kk <= 2 * _BIN_LANES)

        with annotate(_PREPARE):
            if fmask is not None:
                fmask_blocks = jnp.pad(fmask, (0, pad)).reshape(n_tiles, it)
            else:
                fmask_blocks = jnp.ones((n_tiles, it), jnp.bool_)

        def step(carry, inp):
            best_v, best_i = carry
            db_blk, sq_blk, base, mask_blk = inp
            with annotate(_DISTANCE):
                dists = _expanded_block(q, db_blk, q_sq, sq_blk, mt)
                dists = jnp.where(mask_blk[None, :], dists, pad_val)
            with annotate(_TILE_CUT):
                if use_bins:
                    # EXACT unless ≥3 of a query's true top-k collide in
                    # one of the 128 stride bins of one tile (p ≈ 2e-6
                    # per query at k=10; impl="sort" forces the
                    # guaranteed path). Bin positions resolve
                    # arithmetically — no gathers.
                    tv, ti = _two_best_per_bin(dists, select_min)
                else:
                    tv, ti = _select_k(dists, kk, select_min=select_min)
            with annotate(_MERGE):
                ti = ti.astype(idt) + base
                cat_v = jnp.concatenate([best_v, tv], axis=1)
                cat_i = jnp.concatenate([best_i, ti], axis=1)
                nv, pos = _top_k_merge(cat_v, k, select_min)
                ni = jnp.take_along_axis(cat_i, pos, axis=1)
            return (nv, ni), None

        # global ids = tile base + in-tile position: the bases (and the
        # add) run in the policy dtype of the FULL row count (core.ids) —
        # base values reach n, which overflows int32 past 2³¹ rows even
        # though every in-tile position fits it
        idt = _ids.id_dtype(n)
        with annotate(_PREPARE):
            init_v = jnp.full((m, k), pad_val, jnp.float32)
            init_i = jnp.zeros((m, k), idt)
            bases = jnp.arange(n_tiles, dtype=idt) * it
        (vals, idx), _ = lax.scan(
            step, (init_v, init_i), (db_blocks, sq_blocks, bases, fmask_blocks))
        return _finalize(vals, idx)

    # general metrics: full pairwise (row-tiled internally) + select
    with annotate(_DISTANCE):
        dists = pairwise_distance(queries, index.dataset, metric=mt,
                                  metric_arg=index.metric_arg)
        if fmask is not None:
            dists = jnp.where(fmask[None, :], dists,
                              jnp.inf if select_min else -jnp.inf)
    return _finalize(*_select_k(dists, k, select_min=select_min))


def knn_arrays(
    dataset: jax.Array,
    queries: jax.Array,
    k: int,
    metric="euclidean",
    metric_arg: float = 2.0,
) -> Tuple[jax.Array, jax.Array]:
    """One-shot build+search convenience (mirrors pylibraft's functional
    ``brute_force.knn``)."""
    return knn(build(dataset, metric=metric, metric_arg=metric_arg), queries, k)
