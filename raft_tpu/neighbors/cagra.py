"""CAGRA — graph-based ANN: knn-graph build + greedy traversal search.

TPU-native re-design of ``raft::neighbors::cagra`` (cagra.cuh:354;
build detail/cagra/cagra_build.cuh:47-89; optimize graph_core.cuh;
search cagra_search.cuh:105 + search_single_cta_kernel-inl.cuh). Paper:
arXiv:2308.15136 (cited in reference README.md:348). Design mapping:

- **build**: knn-graph from IVF-PQ search over the dataset itself + exact
  refine (the reference's default path, cagra_build.cuh:89-173), then
  ``optimize``: rank-based detourable-edge pruning + reverse-edge
  augmentation (graph_core.cuh) — expressed as batched gather/compare
  tensor ops instead of per-edge CUDA kernels;
- **search**: the reference runs one CTA per query doing a data-dependent
  greedy walk with a visited hashmap and a bitonic itopk buffer. A
  lockstep-SIMD machine wants *fixed-shape* iterations: we batch all
  queries and run a ``lax.while_loop`` whose body expands
  ``search_width`` parents per query (gather graph rows → gather vectors
  → one batched MXU contraction → mask-dedupe against the itopk buffer →
  ``top_k`` merge), with per-entry visited bits replacing the hashmap.
  Iterations stop when every query's top-k is settled (all-parents-
  visited), bounded by ``max_iterations``.

The itopk buffer doubles as the visited-dedup set: a candidate already in
the buffer is marked +inf before the merge. Entries are (dist, id,
visited-bit); parents are the best unvisited entries.
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
from raft_tpu.core.tracing import traced, span
from raft_tpu.core import ids as _ids
from raft_tpu.core import serialize as ser
from raft_tpu.distance.types import DistanceType, resolve_metric
from raft_tpu.matrix import select_k as _select_k
from raft_tpu.neighbors import ivf_pq as _ivf_pq
from raft_tpu.neighbors.refine import refine as _refine
from raft_tpu.utils.precision import get_precision

_SERIAL_VERSION = 3  # v3: + int8 scalar-quantized traversal rows


@dataclasses.dataclass
class IndexParams:
    """reference: ``cagra::index_params`` (cagra_types.hpp:47-60)."""

    intermediate_graph_degree: int = 128
    graph_degree: int = 64
    metric: str = "sqeuclidean"
    # "auto" → "cluster": the TPU-native cluster-blocked exact self-kNN
    # (see cluster_knn_graph). The reference's two build algos remain
    # selectable: "ivf_pq" (ANN self-search + refine, cagra_build.cuh:89)
    # and "nn_descent" (GNND).
    build_algo: str = "auto"  # | "cluster" | "ivf_pq" | "nn_descent"
    nn_descent_niter: int = 20
    # cluster build algo tuning (cluster_knn_graph): each row's exact
    # candidate scan covers the members of its list's `knn_neighborhood`
    # nearest lists of ~`knn_rows_per_list` rows each. On data whose
    # true neighborhoods span many kmeans cells (e.g. thousands of tiny
    # natural clusters), 16 lists cover only ~0.89 of true edges —
    # raising the neighborhood (or shrinking lists) trades build FLOPs
    # for graph recall exactly like IVF n_probes at search time
    knn_rows_per_list: int = 1024
    knn_neighborhood: int = 16
    # graph-BUILD dimensionality: 0 = full-d; "auto" (-1) projects
    # wide datasets (d > 256) onto a random orthonormal 128-d basis
    # for the candidate scans only — the cluster-blocked build's block
    # gathers scale with d (≈96 GB of HBM traffic at 1M×960), while
    # 128-d projections preserve neighbor RANKS well enough for graph
    # candidates; the searched dataset stays full precision
    build_projection_dim: int = -1  # -1 auto | 0 off | explicit dim
    # store int8 scalar-quantized rows beside the f32 dataset (the
    # CAGRA-Q compression analog). OPT-IN like the reference's
    # compression param: it costs +n·d bytes of HBM and, via
    # SearchParams.traverse="auto", changes default search results
    # (int8 traversal trades ~3e-3 recall for ~1.8×/hop bandwidth)
    quantize_dataset: bool = False
    seed: int = 0


@dataclasses.dataclass
class SearchParams:
    """reference: ``cagra::search_params`` (cagra_types.hpp:54-112).

    ``num_seeds``: random entry points sampled per query (the
    ``num_random_samplings``/rand_xor_mask analog). 0 → auto. On an
    index with cluster-seeded entries (default build; see CagraIndex)
    the auto count is max(itopk, 512) random pads on top of the
    nearest-cluster entry points — coverage comes from the entries, not
    the randoms. Without entries (reference build algos) the auto count
    scales with n (max(2·itopk, min(2048, n/64))): a graph over
    strongly clustered data is near-disconnected across clusters, so
    greedy traversal only finds a query's cluster if some random entry
    lands in it — the miss probability (1 - c/n_clusters)^seeds is the
    recall floor (measured: recall 0.35 at n=100k with 128 seeds on
    316-cluster data)."""

    itopk_size: int = 64
    search_width: int = 4
    max_iterations: int = 0   # 0 → auto: ceil(itopk/search_width) * 2
    query_tile: int = 1024
    seed: int = 0             # entry-point sampling (rand_xor_mask analog)
    num_seeds: int = 0        # 0 → auto (see class docstring)
    # cluster-seeded entries: how many nearest clusters contribute
    # entry points (indexes built by the cluster algo). On many-tiny-
    # cluster data the query's true neighborhood spans more kmeans
    # cells than 4 — raising this widens initial coverage the same way
    # n_probes does for IVF (cost: entry_clusters·E seed distances)
    entry_clusters: int = 4
    # traversal dataset precision: "auto" uses the index's int8
    # scalar-quantized rows when present (the CAGRA-Q direction —
    # traversal is HBM-gather-bound, int8 rows move 4× fewer bytes,
    # measured ~1.8× faster per hop) with an exact f32 re-rank of the
    # final buffer; "f32" forces full-precision traversal
    traverse: str = "auto"    # | "f32" | "int8"
    # within-candidate dedup strategy: "pairwise" materializes the
    # [t, C, C] equality mask, "sort" uses two C-wide argsorts
    dedup: str = "pairwise"   # | "sort"


class CagraIndex(flax.struct.PyTreeNode):
    """reference: ``cagra::index`` (cagra_types.hpp).

    ``centers``/``entry_ids`` are a TPU-native extension the cluster
    build algo provides for free: greedy graph traversal over strongly
    clustered data only reaches a query's cluster if an entry point
    lands in it, so random entries put a coverage floor on recall
    (≈ 1 − e^{−seeds/n_clusters}). Seeding from the nearest clusters'
    members removes that floor AND needs far fewer seed distances.
    ``None`` (reference build algos) falls back to random entries."""

    dataset: jax.Array   # [n, dim]
    graph: jax.Array     # [n, graph_degree] i32
    metric: str = flax.struct.field(pytree_node=False, default="sqeuclidean")
    centers: Optional[jax.Array] = None    # [n_lists, dim] f32
    entry_ids: Optional[jax.Array] = None  # [n_lists, E] i32, -1 pad
    # int8 scalar-quantized rows for gather-bound traversal (CAGRA-Q
    # analog): x ≈ q_zero + q_scale · code, per-dimension affine
    dataset_q: Optional[jax.Array] = None  # [n, dim] int8
    q_scale: Optional[jax.Array] = None    # [dim] f32
    q_zero: Optional[jax.Array] = None     # [dim] f32

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]

    @property
    def graph_degree(self) -> int:
        return self.graph.shape[1]


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

@traced("raft_tpu.cagra.build_knn_graph")
def build_knn_graph(dataset: jax.Array, k: int, metric: str = "sqeuclidean",
                    seed: int = 0, search_batch: int = 16384) -> jax.Array:
    """k-NN graph via IVF-PQ self-search + exact refine
    (reference: cagra_build.cuh:89 build_knn_graph — ivf_pq::build, batched
    search with gpu_top_k = k·refine_rate :102, refine :173).

    The self-search runs in ``search_batch`` query chunks, as the
    reference does: one all-rows batch would give the grouped scan an
    O(n·n_probes/n_lists) per-list queue and blow HBM at 100k+ rows."""
    x = jnp.asarray(dataset, jnp.float32)
    n, d = x.shape
    n_lists = max(8, min(1024, int(np.sqrt(n) / 2) or 8))
    pq_dim = max(8, min(d, -(-d // 2 // 8) * 8))
    idx = _ivf_pq.build(x, _ivf_pq.IndexParams(
        n_lists=n_lists, pq_dim=pq_dim, pq_bits=8, metric=metric,
        kmeans_trainset_fraction=min(1.0, 10.0 * n_lists / n + 0.1),
        seed=seed))
    gpu_top_k = min(n, 2 * (k + 1))  # refine_rate 2
    n_probes = max(2, n_lists // 8)
    sp = _ivf_pq.SearchParams(n_probes=n_probes)
    b = min(search_batch, n)
    knn_parts = []
    for start in range(0, n, b):
        q = x[start:start + b]
        if q.shape[0] < b:  # pad the tail chunk: one compiled shape
            q = jnp.pad(q, ((0, b - q.shape[0]), (0, 0)))
        _, cand = _ivf_pq.search(idx, q, gpu_top_k, sp)
        # the exact re-rank rides neighbors.refine's dispatch tier (the
        # fused gather-refine kernel once gpu_top_k reaches the
        # oversampled regime; XLA einsum below it)
        _, ids = _refine(x, q, cand, k + 1, metric=metric)
        knn_parts.append(ids)
    knn_ids = jnp.concatenate(knn_parts, axis=0)[:n]
    return _drop_self_edges(knn_ids, n, k)


@partial(jax.jit, static_argnames=("k", "n_lists", "T", "chunk", "ip"))
def _cluster_blocked_knn(packed, pids, centers, row_list, row_slot,
                         k: int, n_lists: int, T: int, chunk: int,
                         ip: bool):
    """Exact kNN of every row against its cluster neighborhood — one
    jitted program. ``packed [n_lists, L, d]`` / ``pids [n_lists, L]``
    are the balanced-kmeans-packed rows; each list's members scan the
    members of its ``T`` nearest lists with one batched MXU contraction
    per list chunk, and ``approx_min_k`` (the TPU-native top-k) selects
    ``k`` candidates per row. Results return in row order via the
    (list, slot) addresses."""
    nbc = lax.dot_general(centers, centers, (((1,), (1,)), ((), ())),
                          precision=get_precision(),
                          preferred_element_type=jnp.float32)
    c_sq = jnp.sum(centers * centers, axis=1)
    cd = c_sq[:, None] + c_sq[None, :] - 2.0 * nbc
    _, nbrs = lax.top_k(-cd, T)                            # [n_lists, T]

    L = packed.shape[1]
    n_chunks = -(-n_lists // chunk)
    nsp = n_chunks * chunk
    lists_pad = jnp.pad(jnp.arange(n_lists, dtype=jnp.int32),
                        (0, nsp - n_lists))

    def scan_chunk(ls):
        mem = packed[ls].astype(jnp.float32)               # [C, L, d]
        mids = pids[ls]                                    # [C, L]
        nb = nbrs[ls]                                      # [C, T]
        cand = packed[nb].astype(jnp.float32).reshape(
            ls.shape[0], T * L, -1)                        # [C, T·L, d]
        cids = pids[nb].reshape(ls.shape[0], T * L)        # [C, T·L]
        s = jnp.einsum("cld,cmd->clm", mem, cand,
                       precision=get_precision(),
                       preferred_element_type=jnp.float32)
        if ip:
            score = s                                      # maximize
        else:
            m_sq = jnp.sum(mem * mem, axis=-1)
            q_sq = jnp.sum(cand * cand, axis=-1)
            score = -(m_sq[:, :, None] + q_sq[:, None, :] - 2.0 * s)
        bad = (cids[:, None, :] < 0) | (cids[:, None, :] == mids[:, :, None])
        score = jnp.where(bad, -jnp.inf, score)
        _, pos = jax.lax.approx_max_k(
            score.reshape(-1, T * L), k, recall_target=0.95)
        ids = jnp.take_along_axis(
            jnp.repeat(cids, L, axis=0), pos, axis=1)      # [C·L, k]
        return ids.reshape(ls.shape[0], L, k)

    res = lax.map(scan_chunk, lists_pad.reshape(n_chunks, chunk))
    res = res.reshape(nsp, L, k)
    return res[row_list, row_slot]                         # [n, k]


@partial(jax.jit, static_argnames=("k", "ip", "chunk"))
def _overflow_knn(x, packed, pids, rows, lists, k: int, ip: bool,
                  chunk: int):
    """Exact kNN of overflow rows against their own cluster blocks:
    q [o, d] vs packed[lists] [o, L, d] → ids [o, k]. Chunked over rows
    so the [chunk, L, d] block gather stays memory-bounded — heavy skew
    (the only trigger of this path) can overflow many rows at once."""
    L = packed.shape[1]

    def one_chunk(args):
        rows_c, lists_c = args
        q = x[rows_c].astype(jnp.float32)                 # [c, d]
        blk = packed[lists_c].astype(jnp.float32)         # [c, L, d]
        bids = pids[lists_c]                              # [c, L]
        s = jnp.einsum("od,old->ol", q, blk,
                       precision=get_precision(),
                       preferred_element_type=jnp.float32)
        if ip:
            score = s
        else:
            score = -(jnp.sum(blk * blk, -1) - 2.0 * s)   # rank-equivalent
        bad = (bids < 0) | (bids == rows_c[:, None])
        score = jnp.where(bad, -jnp.inf, score)
        _, pos = lax.top_k(score, k)
        return jnp.take_along_axis(bids, pos, axis=1).astype(jnp.int32)

    o = rows.shape[0]
    if o <= chunk:
        return one_chunk((rows, lists))
    n_chunks = -(-o // chunk)
    pad = n_chunks * chunk - o
    rows_p = jnp.pad(rows, (0, pad), mode="edge")
    lists_p = jnp.pad(lists, (0, pad), mode="edge")
    out = lax.map(one_chunk, (rows_p.reshape(n_chunks, chunk),
                              lists_p.reshape(n_chunks, chunk)))
    return out.reshape(n_chunks * chunk, k)[:o]


def cluster_knn_graph(dataset: jax.Array, k: int, metric: str = "sqeuclidean",
                      seed: int = 0, rows_per_list: int = 1024,
                      neighborhood: int = 16, return_entries: bool = False,
                      centers_from: Optional[jax.Array] = None):
    """TPU-native k-NN graph: cluster-blocked exact self-kNN.

    The reference builds CAGRA's knn graph by ANN self-search (IVF-PQ +
    refine, cagra_build.cuh:89) — a per-query gather/scan structure. On
    TPU the natural shape is block-dense: balanced-kmeans the rows into
    ~n/1024 lists, then give each list's members EXACT brute-force
    distances against the members of its ``neighborhood`` nearest lists
    — large square MXU contractions, no codes, no refine pass. Candidate
    coverage matches an IVF search probing ``neighborhood`` lists; the
    distances are exact f32 (better rank quality than PQ+refine), and
    graph build time at 1M×128 drops from tens of minutes to ~1 minute
    on a v5e.
    """
    x = jnp.asarray(dataset, jnp.float32)
    n, d = x.shape
    mt = resolve_metric(metric)
    ip = mt == DistanceType.InnerProduct
    if n <= (1 << 14) or n // rows_per_list < 4:
        # small corpus: plain exact kNN (one tiled program)
        from raft_tpu.neighbors import brute_force as _bf

        idx = _bf.build(x, metric="inner_product" if ip else "sqeuclidean")
        _, knn_ids = _bf.knn(idx, x, min(n, k + 1))
        g = _drop_self_edges(knn_ids, n, k)
        return (g, None, None) if return_entries else g

    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
    from raft_tpu.neighbors import ivf_common as ic
    from raft_tpu.neighbors.ivf_flat import _fit_list_size

    n_lists = max(8, n // rows_per_list)
    km = KMeansBalancedParams(n_iters=10, metric="l2", seed=seed)
    n_train = min(n, max(n_lists * 4, n // 4))
    if n_train < n:
        rng = np.random.default_rng(seed)
        trainset = x[jnp.asarray(np.sort(rng.choice(n, n_train, replace=False)))]
    else:
        trainset = x
    centers = kmeans_balanced.fit(trainset, n_lists, km)
    labels = kmeans_balanced.predict(centers, x, km)
    counts = np.bincount(np.asarray(labels), minlength=n_lists)
    L = _fit_list_size(counts, max(1, n // n_lists), 4.0)
    (packed,), pids, _, dropped, (row_list, row_slot) = ic.pack_lists_jit(
        [x], labels, jnp.arange(n, dtype=jnp.int32),
        n_lists=n_lists, L=L, fill_values=[jnp.zeros((), x.dtype)])
    n_over = int(dropped)
    overflow_rows = np.nonzero(np.asarray(row_slot) >= L)[0] if n_over else None
    row_slot = jnp.clip(row_slot, 0, L - 1)  # overflow rows borrow slot L-1

    T = min(neighborhood, n_lists)
    kk = min(k, T * L - 1)
    # chunk bound: [C, L, T·L] f32 distance block under ~192 MB
    chunk = max(1, (192 << 20) // max(1, L * T * L * 4))
    graph = _cluster_blocked_knn(packed, pids, centers, row_list, row_slot,
                                 kk, n_lists, T, min(chunk, n_lists), ip)
    if n_over:
        # overflow rows never entered a packed list: the blocked scan
        # would hand them slot L-1's neighbor list (a different vector's
        # edges) and they receive no incoming edges either. Patch them
        # with an exact scan of their own cluster block — rare (the
        # packer already warned), so one padded side pass is cheap.
        from raft_tpu.core import logging as _log
        _log.warn("cluster_knn_graph: %d rows overflowed their list; "
                  "patching their graph rows via an in-cluster scan",
                  n_over)
        o_pad = max(8, 1 << (n_over - 1).bit_length())
        o_idx = np.pad(overflow_rows, (0, o_pad - n_over), mode="edge")
        o_rows = jnp.asarray(o_idx)
        o_chunk = max(8, (192 << 20) // max(1, L * d * 4))
        ov = _overflow_knn(x, packed, pids, o_rows,
                           row_list[o_rows], min(kk, L - 1), ip,
                           min(o_pad, -(-o_chunk // 8) * 8))
        if ov.shape[1] < kk:
            ov = jnp.pad(ov, ((0, 0), (0, kk - ov.shape[1])), mode="edge")
        graph = graph.at[o_rows[:n_over]].set(ov[:n_over])
    if kk < k:
        graph = jnp.pad(graph, ((0, 0), (0, k - kk)), mode="edge")
    graph = graph.astype(jnp.int32)
    if return_entries:
        if centers_from is not None:
            # projected build (see IndexParams.build_projection_dim):
            # search seeds score queries against centers in FULL space,
            # so recompute them as per-list means of the original rows
            from raft_tpu.cluster.kmeans import _update_centroids

            centers, _ = _update_centroids(
                centers_from.astype(jnp.float32),
                jnp.ones((n,), jnp.float32), labels, n_lists,
                jnp.zeros((n_lists, centers_from.shape[1]), jnp.float32))
        return graph, centers, pids[:, :min(32, L)]
    return graph


def _drop_self_edges(knn_ids: jax.Array, n: int, k: int) -> jax.Array:
    """Stable-partition self hits out of a [n, >=k+1] id table → [n, k]."""
    self_col = knn_ids == jnp.arange(n, dtype=knn_ids.dtype)[:, None]
    order = jnp.argsort(self_col, axis=1, stable=True)
    cleaned = jnp.take_along_axis(knn_ids, order, axis=1)[:, :k]
    return cleaned.astype(jnp.int32)


@partial(jax.jit, static_argnames=("out_degree",))
def optimize_graph(knn_graph: jax.Array, out_degree: int) -> jax.Array:
    """Detourable-edge pruning + reverse-edge augmentation
    (reference: graph_core.cuh optimize, 572 LoC; CAGRA paper §4.1).

    Edge u→v (rank i in u's list) is *detourable* through w (rank j<i) if
    v also appears in w's own neighbor list at a rank < i — i.e. the
    two-hop path u→w→v uses strictly closer edges. Edges with the fewest
    detour paths are kept; half the output degree is then filled with
    reverse edges (incoming links), which CAGRA shows is what makes the
    graph navigable.
    """
    n, D = knn_graph.shape
    d_half = out_degree // 2

    def detour_counts(u_list):
        # u_list: [D] neighbor ids sorted by distance rank
        nbr_lists = knn_graph[u_list]                     # [D, D] lists of w=u_list[j]
        # pos[j, i] = rank of u_list[i] in w_j's list (D if absent)
        eq = nbr_lists[:, :, None] == u_list[None, None, :]  # [D(j), D(pos), D(i)]
        pos = jnp.min(jnp.where(eq, jnp.arange(D)[None, :, None], D), axis=1)  # [D(j), D(i)]
        ranks = jnp.arange(D)
        # detour via w_j for edge i: j < i  AND  pos[j, i] < i
        detour = (ranks[:, None] < ranks[None, :]) & (pos < ranks[None, :])
        return jnp.sum(detour, axis=0)                    # [D] counts per edge i

    counts = lax.map(detour_counts, knn_graph, batch_size=256)  # [n, D]
    # keep lowest-detour-count edges, tie-broken by distance rank
    score = counts.astype(jnp.int32) * D + jnp.arange(D, dtype=jnp.int32)[None, :]
    keep = jnp.argsort(score, axis=1)[:, :out_degree]
    pruned = jnp.take_along_axis(knn_graph, keep, axis=1)  # [n, out_degree]

    # reverse-edge augmentation: for each node, gather up to d_half incoming
    # edges (from the pruned forward graph) and splice them after the
    # d_half best forward edges (graph_core.cuh rev_graph).
    fwd = pruned[:, :d_half]
    src = jnp.repeat(_ids.make_ids(n)[:, None], d_half, 1).reshape(-1)
    dst = fwd.reshape(-1)
    # count and slot reverse edges per destination node
    order = jnp.argsort(dst, stable=True)
    dst_s, src_s = dst[order], src[order]
    # position of each edge within its destination group
    first_idx = jnp.searchsorted(dst_s, _ids.make_ids(n))
    slot = jnp.arange(dst_s.shape[0]) - first_idx[dst_s]
    # table dtype follows the source ids' policy width (core.ids) — a
    # hard int32 table would silently truncate int64 node ids through
    # the scatter at n ≥ 2³¹ (jnp .at[].set casts, it doesn't error)
    rev = jnp.full((n, d_half), -1, src.dtype)
    valid = slot < d_half
    # out-of-quota reverse edges write to row n → dropped
    rev = rev.at[jnp.where(valid, dst_s, n),
                 jnp.clip(slot, 0, d_half - 1)].set(src_s, mode="drop")
    # final graph: best forward half + reverse half (fall back to forward
    # edges where no reverse edge exists)
    fallback = pruned[:, d_half:out_degree]
    merged = jnp.where(rev >= 0, rev, fallback)
    return jnp.concatenate([fwd, merged], axis=1)


@jax.jit
def _quantize_rows(x: jax.Array):
    """Per-dimension affine int8 scalar quantization of the dataset —
    the traversal-side compression of the reference's CAGRA-Q
    direction (vpq_dataset / cagra compression): x ≈ zero + scale·code,
    codes in [-127, 127]. Costs n·d bytes; search gathers these rows
    instead of f32 (4× fewer bytes on the gather-bound hop) and
    re-ranks the final buffer exactly."""
    mn = jnp.min(x, axis=0)
    mx = jnp.max(x, axis=0)
    zero = 0.5 * (mn + mx)
    scale = jnp.maximum((mx - mn) / 254.0, 1e-12)
    codes = jnp.clip(jnp.round((x - zero) / scale), -127, 127)
    return codes.astype(jnp.int8), scale.astype(jnp.float32), zero.astype(jnp.float32)


@traced("raft_tpu.cagra.build")
def build(dataset: jax.Array, params: Optional[IndexParams] = None) -> CagraIndex:
    """Build (reference: cagra::build, cagra.cuh — knn-graph + optimize)."""
    if params is None:
        params = IndexParams()
    mt = resolve_metric(params.metric)
    expects(mt in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                   DistanceType.InnerProduct),
            "cagra supports sqeuclidean/euclidean/inner_product")
    x = jnp.asarray(dataset, jnp.float32)
    n = x.shape[0]
    inter_d = min(params.intermediate_graph_degree, n - 1)
    out_d = min(params.graph_degree, inter_d)
    algo = params.build_algo
    if algo == "auto":
        algo = "cluster"
    centers = entry_ids = None
    proj_d = params.build_projection_dim
    if proj_d == -1:
        proj_d = 128 if x.shape[1] > 256 else 0
    if proj_d and proj_d < x.shape[1] and mt != DistanceType.InnerProduct:
        # random orthonormal projection for the BUILD scans only (see
        # IndexParams.build_projection_dim); L2 ranks are approximately
        # preserved, and optimize_graph's detour pruning only consumes
        # ranks. ip metric skips it (projection distorts raw dot
        # products more than distances).
        g = jax.random.normal(jax.random.PRNGKey(params.seed ^ 0x5EED),
                              (x.shape[1], proj_d), jnp.float32)
        r, _ = jnp.linalg.qr(g)
        x_build = jnp.matmul(x, r, precision=get_precision())
    else:
        x_build = x
    with span("knn_graph") as _sp:
        if algo == "nn_descent":
            from raft_tpu.neighbors.nn_descent import build_knn_graph as _nnd
            knn = _nnd(x_build, inter_d, metric=mt.value,
                       n_iters=params.nn_descent_niter, seed=params.seed)
        elif algo == "cluster":
            knn, centers, entry_ids = cluster_knn_graph(
                x_build, inter_d, metric=mt.value, seed=params.seed,
                rows_per_list=params.knn_rows_per_list,
                neighborhood=params.knn_neighborhood,
                return_entries=True,
                centers_from=x if x_build is not x else None)
        else:
            knn = build_knn_graph(x, inter_d, metric=mt.value,
                                  seed=params.seed)
        _sp.attach(knn)
    with span("optimize") as _sp:
        graph = optimize_graph(knn, out_d)
        _sp.attach(graph)
    codes = scale = zero = None
    if params.quantize_dataset:
        with span("quantize") as _sp:
            codes, scale, zero = _quantize_rows(x)
            _sp.attach(codes)
    return CagraIndex(dataset=x, graph=graph, metric=mt.value,
                      centers=centers, entry_ids=entry_ids,
                      dataset_q=codes, q_scale=scale, q_zero=zero)


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "itopk_size", "search_width",
                                   "max_iterations", "query_tile", "seed",
                                   "num_seeds", "use_q", "dedup",
                                   "entry_clusters"))
def _search_impl(index: CagraIndex, queries: jax.Array, k: int,
                 itopk_size: int, search_width: int, max_iterations: int,
                 query_tile: int, seed: int = 0, num_seeds: int = 0,
                 use_q: bool = False, dedup: str = "pairwise",
                 filter_bits=None, entry_clusters: int = 4):
    mt = resolve_metric(index.metric)
    ip = mt == DistanceType.InnerProduct
    sqrt_out = mt == DistanceType.L2SqrtExpanded
    x = index.dataset
    n, d = x.shape
    deg = index.graph_degree
    m = queries.shape[0]
    q_all = jnp.asarray(queries, jnp.float32)
    BIG = jnp.float32(jnp.inf)
    # node ids (traversal buffer, seeds, neighbor lists) ride the policy
    # dtype of the dataset row count (core.ids): int32 until n ≥ 2³¹
    idt = _ids.id_dtype(n)

    def dists_to(q, ids):
        """q [t, d], ids [t, C] → metric scores [t, C] (lower = better).

        Traversal is HBM-gather-bound (512 B random rows measured
        ~32 GB/s); ``use_q`` gathers the int8 scalar-quantized rows
        instead (4× fewer bytes, ~1.8× faster per hop — the CAGRA-Q
        direction, search epilogue re-ranks exactly). Candidate norms
        come from the gathered rows: a separate ``x_sq[ids]`` POINTWISE
        gather costs more than the whole row gather."""
        if use_q:
            rows = (index.q_zero[None, None, :]
                    + index.dataset_q[ids].astype(jnp.float32)
                    * index.q_scale[None, None, :])       # [t, C, d]
        else:
            rows = x[ids]                                 # [t, C, d]
        s = jnp.einsum("td,tcd->tc", q, rows,
                       precision=get_precision(),
                       preferred_element_type=jnp.float32)
        if ip:
            return -s
        nsq = jnp.sum(rows * rows, axis=-1)
        return jnp.maximum(jnp.sum(q * q, 1)[:, None] + nsq - 2.0 * s, 0.0)

    base_key = jax.random.PRNGKey(seed)

    def search_tile(q, qstart):
        t = q.shape[0]
        # entry points are a per-QUERY pseudo-random function of (seed,
        # global query index) — the reference hashes query id through
        # rand_xor_mask the same way — so results are independent of query
        # tiling and entry sets are decorrelated across queries
        qidx = qstart + jnp.arange(t, dtype=jnp.uint32)
        keys = jax.vmap(lambda i: jax.random.fold_in(base_key, i))(qidx)
        if index.centers is not None:
            # cluster-seeded entries (see CagraIndex): members of the
            # query's nearest clusters, padded with random draws — the
            # random-only floor (1 − e^{−seeds/n_clusters}) disappears
            # and far fewer seed distances are needed
            cts = index.centers
            qc = jnp.einsum("td,ld->tl", q, cts,
                            precision=get_precision(),
                            preferred_element_type=jnp.float32)
            c_score = qc if ip else 2.0 * qc - jnp.sum(cts * cts, 1)[None, :]
            c_sel = min(entry_clusters, cts.shape[0])
            _, top_l = lax.top_k(c_score, c_sel)           # [t, c_sel]
            ent = index.entry_ids[top_l].reshape(t, -1)    # [t, c_sel·E]
            n_rand = max(num_seeds or max(itopk_size, 512), itopk_size)
            # total seeds rounded UP to a multiple of 128 so the seed-
            # distance chunking below always finds a divisor (c_sel·E is
            # not 128 for narrow entry tables)
            n_seed = -(-(ent.shape[1] + n_rand) // 128) * 128
            ent = jnp.concatenate(
                [ent, jnp.full((t, n_seed - ent.shape[1]), -1, ent.dtype)],
                axis=1)
            rnd = jax.vmap(
                lambda kk: jax.random.randint(kk, (n_seed,), 0, n,
                                              dtype=idt))(keys)
            init_ids = jnp.where(ent >= 0, ent.astype(idt), rnd)
        else:
            # oversample candidates and keep the best itopk — the
            # reference's random_sampling makes multiple hashed draws per
            # itopk slot the same way (compute_random_samples /
            # num_random_samplings). The count scales with n (see
            # SearchParams.num_seeds): entry coverage is the recall floor
            # on clustered data. Clamp: the buffer init takes top itopk
            # of the seeds, so fewer seeds than itopk slots would break
            # lax.top_k; round to a multiple of 128 so the seed phase can
            # chunk evenly
            n_seed = max(num_seeds or max(2 * itopk_size, min(2048, n // 64)),
                         itopk_size)
            n_seed = -(-n_seed // 128) * 128
            init_ids = jax.vmap(
                lambda kk: jax.random.randint(kk, (n_seed,), 0, n,
                                              dtype=idt))(keys)
        # sampled with replacement: demote duplicate entry slots so an id
        # can never surface twice in the buffer. Sort-based dedup — the
        # quadratic pairwise mask would be O(n_seed²) per query
        order = jnp.argsort(init_ids, axis=1)
        sorted_ids = jnp.take_along_axis(init_ids, order, axis=1)
        dup_sorted = jnp.concatenate(
            [jnp.zeros((t, 1), jnp.bool_),
             sorted_ids[:, 1:] == sorted_ids[:, :-1]], axis=1)
        inv = jnp.argsort(order, axis=1)
        dup0 = jnp.take_along_axis(dup_sorted, inv, axis=1)
        # chunk the seed-distance gather: at n_seed=2048 an unchunked
        # x[init_ids] would materialize [t, n_seed, d] (GBs at large d);
        # lax.map bounds the intermediate to one chunk
        if n_seed > 512:
            c = 512
            while n_seed % c:
                c -= 128  # n_seed is a multiple of 128
            ids_r = jnp.transpose(
                init_ids.reshape(t, n_seed // c, c), (1, 0, 2))
            seed_d = jnp.transpose(
                lax.map(lambda ii: dists_to(q, ii), ids_r),
                (1, 0, 2)).reshape(t, n_seed)
        else:
            seed_d = dists_to(q, init_ids)
        seed_d = jnp.where(dup0, BIG, seed_d)
        _, best = lax.top_k(-seed_d, itopk_size)
        init_ids = jnp.take_along_axis(init_ids, best, axis=1)
        buf_d = jnp.take_along_axis(seed_d, best, axis=1)
        if filter_bits is not None:
            from raft_tpu.neighbors.sample_filter import passes

            # filtered vectors score +inf so they never rank in the itopk
            # nor get expanded — the exclusion point the reference's
            # cagra sample_filter hooks
            buf_d = jnp.where(passes(filter_bits, init_ids), buf_d, BIG)
        buf_i = init_ids.astype(idt)
        order = jnp.argsort(buf_d, axis=1)
        buf_d = jnp.take_along_axis(buf_d, order, 1)
        buf_i = jnp.take_along_axis(buf_i, order, 1)
        buf_v = jnp.zeros((t, itopk_size), jnp.bool_)     # visited bits

        def cond(state):
            _, _, buf_v, it = state
            # stop when every query's whole itopk buffer is visited
            # (the reference iterates until the itopk converges)
            return (it < max_iterations) & ~jnp.all(buf_v)

        def body(state):
            buf_d, buf_i, buf_v, it = state
            # freeze settled queries (whole buffer visited): their updates
            # are discarded, so results don't depend on query tiling
            frozen = jnp.all(buf_v, axis=1)
            old = (buf_d, buf_i, buf_v)
            # 1. pick search_width best unvisited parents
            cand_score = jnp.where(buf_v, BIG, buf_d)
            _, parent_pos = lax.top_k(-cand_score, search_width)   # [t, W]
            parent_ids = jnp.take_along_axis(buf_i, parent_pos, 1)
            parent_valid = jnp.take_along_axis(cand_score, parent_pos, 1) < BIG
            # mark visited
            buf_v = buf_v.at[jnp.arange(t)[:, None], parent_pos].set(True)
            # 2. expand: gather graph rows of parents → [t, W·deg]
            nbrs = index.graph[jnp.clip(parent_ids, 0, n - 1)]     # [t, W, deg]
            nbrs = nbrs.reshape(t, search_width * deg)
            nbrs = jnp.where(jnp.repeat(parent_valid, deg, axis=1), nbrs, 0)
            # 3. distances on the MXU
            nd = dists_to(q, nbrs)
            nd = jnp.where(jnp.repeat(parent_valid, deg, axis=1), nd, BIG)
            if filter_bits is not None:
                from raft_tpu.neighbors.sample_filter import passes

                nd = jnp.where(passes(filter_bits, nbrs), nd, BIG)
            # 4. dedupe against the buffer (the visited-hashmap stand-in)
            dup = jnp.any(nbrs[:, :, None] == buf_i[:, None, :], axis=2)
            nd = jnp.where(dup, BIG, nd)
            # dedupe within the candidate set (first occurrence wins):
            # "sort" marks equal-adjacent ids through two C-wide
            # argsorts; "pairwise" lets XLA fuse the [t, C, C] equality
            # mask (cheaper at small C, never materialized)
            if dedup == "sort":
                c_order = jnp.argsort(nbrs, axis=1)
                sorted_ids = jnp.take_along_axis(nbrs, c_order, axis=1)
                dup_s = jnp.concatenate(
                    [jnp.zeros((t, 1), jnp.bool_),
                     sorted_ids[:, 1:] == sorted_ids[:, :-1]], axis=1)
                c_inv = jnp.argsort(c_order, axis=1)
                nd = jnp.where(jnp.take_along_axis(dup_s, c_inv, axis=1),
                               BIG, nd)
            else:
                eq = nbrs[:, :, None] == nbrs[:, None, :]
                earlier = jnp.tril(
                    jnp.ones((search_width * deg,) * 2, jnp.bool_), -1)
                nd = jnp.where(jnp.any(eq & earlier[None], axis=2), BIG, nd)
            # 5. merge into itopk: concat + select
            all_d = jnp.concatenate([buf_d, nd], axis=1)
            all_i = jnp.concatenate([buf_i, nbrs.astype(idt)], axis=1)
            all_v = jnp.concatenate(
                [buf_v, jnp.zeros_like(nd, dtype=jnp.bool_)], axis=1)
            _, pos = lax.top_k(-all_d, itopk_size)
            buf_d = jnp.take_along_axis(all_d, pos, 1)
            buf_i = jnp.take_along_axis(all_i, pos, 1)
            buf_v = jnp.take_along_axis(all_v, pos, 1)
            buf_d = jnp.where(frozen[:, None], old[0], buf_d)
            buf_i = jnp.where(frozen[:, None], old[1], buf_i)
            buf_v = jnp.where(frozen[:, None], old[2], buf_v)
            return buf_d, buf_i, buf_v, it + 1

        buf_d, buf_i, _, _ = lax.while_loop(
            cond, body, (buf_d, buf_i, buf_v, jnp.array(0, jnp.int32)))
        if use_q:
            # exact f32 re-rank of the final buffer: quantization error
            # only ever shuffled candidates WITHIN the buffer; one cheap
            # [t, itopk] row gather restores exact distances and order
            rows = x[jnp.clip(buf_i, 0, n - 1)]           # [t, itopk, d]
            s = jnp.einsum("td,tcd->tc", q, rows,
                           precision=get_precision(),
                           preferred_element_type=jnp.float32)
            if ip:
                exact = -s
            else:
                exact = jnp.maximum(
                    jnp.sum(q * q, 1)[:, None]
                    + jnp.sum(rows * rows, -1) - 2.0 * s, 0.0)
            exact = jnp.where(jnp.isinf(buf_d), BIG, exact)
            _, pos = lax.top_k(-exact, k)
            buf_d = jnp.take_along_axis(exact, pos, axis=1)
            buf_i = jnp.take_along_axis(buf_i, pos, axis=1)
        out_d, out_i = buf_d[:, :k], buf_i[:, :k]
        if filter_bits is not None:
            # inf-score slots are filtered/unreached: mark their ids -1
            # (same pad convention as brute-force/IVF)
            out_i = jnp.where(jnp.isinf(out_d), -1, out_i)
        if ip:
            out_d = -out_d
        elif sqrt_out:
            out_d = jnp.sqrt(out_d)
        return out_d, out_i

    if m <= query_tile:
        return search_tile(q_all, jnp.uint32(0))
    n_tiles = -(-m // query_tile)
    pad = n_tiles * query_tile - m
    qp = jnp.pad(q_all, ((0, pad), (0, 0)))
    starts = (jnp.arange(n_tiles, dtype=jnp.uint32) * query_tile)
    vals, ids = lax.map(lambda args: search_tile(*args),
                        (qp.reshape(n_tiles, query_tile, d), starts))
    return vals.reshape(-1, k)[:m], ids.reshape(-1, k)[:m]


@traced("raft_tpu.cagra.search")
def search(index: CagraIndex, queries: jax.Array, k: int,
           params: Optional[SearchParams] = None,
           filter_bitset: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Search (reference: cagra::search → search_main, cagra_search.cuh:105;
    filtered overload via CagraSampleFilterT).
    ``filter_bitset``: optional packed bitset over dataset rows (see
    neighbors.sample_filter) — cleared bits are excluded."""
    if params is None:
        params = SearchParams()
    expects(queries.ndim == 2 and queries.shape[1] == index.dim,
            "queries must be [m, %d]", index.dim)
    expects(params.traverse in ("auto", "f32", "int8"),
            "traverse must be auto/f32/int8, not %s", params.traverse)
    use_q = (params.traverse == "int8"
             or (params.traverse == "auto" and index.dataset_q is not None))
    if use_q:
        expects(index.dataset_q is not None,
                "traverse='int8' needs an index with quantized rows")
    itopk = max(params.itopk_size, k)
    max_it = params.max_iterations or 2 * (-(-itopk // params.search_width))
    return _search_impl(index, queries, k, itopk, params.search_width,
                        max_it, params.query_tile, seed=params.seed,
                        num_seeds=params.num_seeds, use_q=use_q,
                        dedup=params.dedup, filter_bits=filter_bitset,
                        entry_clusters=params.entry_clusters)


# ---------------------------------------------------------------------------
# serialization (reference: neighbors/cagra_serialize.cuh)
# ---------------------------------------------------------------------------

def save(index: CagraIndex, path: str, include_dataset: bool = True) -> None:
    arrays = {"graph": index.graph}
    if include_dataset:
        arrays["dataset"] = index.dataset
    if index.centers is not None:
        arrays["centers"] = index.centers
        arrays["entry_ids"] = index.entry_ids
    if index.dataset_q is not None:
        arrays["dataset_q"] = index.dataset_q
        arrays["q_scale"] = index.q_scale
        arrays["q_zero"] = index.q_zero
    ser.save_arrays(path, "cagra", _SERIAL_VERSION,
                    {"metric": index.metric}, arrays)


def load(path: str, dataset: Optional[jax.Array] = None) -> CagraIndex:
    version, meta, a = ser.load_arrays(path, "cagra")
    # v1/v2 files lack centers/entry_ids resp. quantized rows (search
    # falls back to random entries / f32 traversal)
    expects(version in (1, 2, _SERIAL_VERSION),
            "unsupported cagra version %d", version)
    ds = jnp.asarray(a["dataset"]) if "dataset" in a else jnp.asarray(dataset)

    def get(name):
        return jnp.asarray(a[name]) if name in a else None

    return CagraIndex(
        dataset=ds, graph=jnp.asarray(a["graph"]), metric=meta["metric"],
        centers=get("centers"), entry_ids=get("entry_ids"),
        dataset_q=get("dataset_q"), q_scale=get("q_scale"),
        q_zero=get("q_zero"))


def serialize_to_hnswlib(index: CagraIndex, path: str,
                         ef_construction: int = 200) -> None:
    """Export the CAGRA graph as an hnswlib-loadable index file
    (reference capability: cagra_serialize serialize_to_hnswlib — a
    flat level-0-only HNSW whose neighbor lists are the CAGRA graph).

    Binary layout follows hnswlib's ``HierarchicalNSW::saveIndex``
    (hnswalg.h): header of size_t/int fields, then per-element level-0
    blocks ``[link_count u16 + pad u16][maxM0 x u32 links][f32 data]
    [u64 label]``, then a zero u32 per element (no upper levels).
    Loadable with ``hnswlib.Index(space, dim).load_index(path)`` where
    space is "l2" for (sq)euclidean and "ip" for inner_product.
    """
    import struct

    expects(resolve_metric(index.metric) in
            (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
             DistanceType.InnerProduct),
            "hnswlib export supports l2/inner_product metrics, not %s",
            index.metric)
    data = np.ascontiguousarray(np.asarray(index.dataset), np.float32)
    graph = np.asarray(index.graph, np.int64)
    n, dim = data.shape
    degree = graph.shape[1]

    max_m0 = degree               # level-0 out-degree = graph degree
    m = max(1, degree // 2)
    data_size = dim * 4
    size_links0 = max_m0 * 4 + 4  # u32 count-word + maxM0 u32 links
    size_per_elem = size_links0 + data_size + 8  # + u64 label
    offset_data = size_links0
    label_offset = size_links0 + data_size
    mult = 1.0 / np.log(max(m, 2))

    # hnswlib reads the first `count` links, so valid ids must be
    # compacted to the front (graph rows can carry interior -1 entries
    # when the knn stage returned fewer than degree candidates)
    valid = graph >= 0
    counts = np.sum(valid, axis=1).astype(np.uint16)
    front = np.argsort(~valid, axis=1, kind="stable")  # valid-first, ordered
    links = np.take_along_axis(np.where(valid, graph, 0), front,
                               axis=1).astype(np.uint32)

    with open(path, "wb") as f:
        f.write(struct.pack("<QQQQQQiIQQQdQ",
                            0,              # offsetLevel0_
                            n,              # max_elements_
                            n,              # cur_element_count
                            size_per_elem,  # size_data_per_element_
                            label_offset,   # label_offset_
                            offset_data,    # offsetData_
                            0,              # maxlevel_
                            0,              # enterpoint_node_
                            m,              # maxM_
                            max_m0,         # maxM0_
                            m,              # M_
                            float(mult),    # mult_
                            ef_construction))
        # level-0 blocks, assembled vectorized then written once
        block = np.zeros((n, size_per_elem), np.uint8)
        block[:, 0:2] = counts[:, None].view(np.uint8).reshape(n, 2)
        block[:, 4:4 + max_m0 * 4] = links.view(np.uint8).reshape(n, -1)
        block[:, offset_data:offset_data + data_size] = data.view(
            np.uint8).reshape(n, -1)
        block[:, label_offset:] = np.arange(n, dtype=np.uint64).view(
            np.uint8).reshape(n, 8)
        f.write(block.tobytes())
        # one u32 per element: no higher-level link lists
        f.write(np.zeros(n, np.uint32).tobytes())
