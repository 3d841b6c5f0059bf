"""Distributed IVF indexes — SPMD sharded build + search over a mesh.

The reference's raison-d'être for its comms stack: raft-dask sharded-index
patterns (SURVEY.md §2.15; raft_dask/common/comms.py:39) where each worker
builds an IVF index over its shard of the dataset and search merges
per-shard top-k candidates (``knn_merge_parts``,
neighbors/detail/knn_merge_parts.cuh). BASELINE config 5 (sharded IVF-PQ,
SIFT-1B on v5e-64) is this module's target shape.

TPU-native structure — everything is ``shard_map`` over one mesh axis:

- **coarse centers**: ONE distributed Lloyd program (local fused-L2
  assign + ``psum``-merged centroid sums — the reference's MNMG kmeans
  allreduce, SURVEY.md §3.5) over the row-sharded dataset, so every
  shard trains against the *global* data distribution, not its slice;
- **codebooks / rotation** (PQ): replicated. Codebooks train on an
  ``all_gather``-ed cross-shard subsample (the reference also trains on
  a trainset fraction, ivf_pq_build.cuh:1511);
- **encode + pack**: per shard, fully on device — ``ivf_common.pack_lists``
  (one stable sort + scatter) replaces the host packers, because inside
  an SPMD program there is no host round-trip. Stored ids are *global*
  row ids (shard offset baked in at build), so search needs no
  translation step;
- **search**: queries replicated; each shard scans its local lists with
  the single-device search kernel, then ``all_gather`` + final select_k
  merges candidates over ICI — the sharded brute-force pattern
  (parallel/knn.py) applied to IVF.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple, Union

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from raft_tpu.core.errors import expects
from raft_tpu.core import ids as _ids
from raft_tpu.cluster import KMeansParams
from raft_tpu.cluster import distributed as dkm
from raft_tpu.distance import SELECT_MIN
from raft_tpu.distance.fused_l2_nn import fused_l2_nn_argmin
from raft_tpu.distance.types import DistanceType, resolve_metric
from raft_tpu.neighbors import ivf_flat as _flat
from raft_tpu.neighbors import ivf_pq as _pq
from raft_tpu.neighbors import ivf_common as ic
from raft_tpu.parallel import merge as _merge
from raft_tpu.parallel.comms import Comms
from raft_tpu.robust import faults as _faults
from raft_tpu.utils.precision import get_precision


class ShardedIvfPq(flax.struct.PyTreeNode):
    """IVF-PQ index sharded over a mesh axis: quantizers replicated,
    packed lists carrying a leading device axis (sharded)."""

    centers: jax.Array        # [n_lists, dim] replicated
    centers_rot: jax.Array    # [n_lists, rot_dim] replicated
    rotation: jax.Array       # [rot_dim, dim] replicated
    codebooks: jax.Array      # [pq_dim, K, pq_len] replicated
    packed_codes: jax.Array   # [n_dev, n_lists, L, nbytes] u8, sharded
    packed_ids: jax.Array     # [n_dev, n_lists, L] i32 global ids, -1 pad
    packed_norms: jax.Array   # [n_dev, n_lists, L] f32
    list_sizes: jax.Array     # [n_dev, n_lists] i32
    metric: str = flax.struct.field(pytree_node=False, default="sqeuclidean")
    pq_bits: int = flax.struct.field(pytree_node=False, default=8)
    pq_dim: int = flax.struct.field(pytree_node=False, default=0)
    # rows per shard of the (padded) BUILD dataset — the global ids baked
    # into packed_ids are rank·shard_rows + local, so the refined search
    # can validate a caller-passed dataset against the build geometry
    # (0 = unknown, for indexes assembled by hand)
    shard_rows: int = flax.struct.field(pytree_node=False, default=0)
    # the GLOBAL list capacity a single-host build of the same dataset
    # would fit (stamped by parallel.build's distributed builders; 0 =
    # unknown) — parallel.build.assemble_ivf_pq truncates the rank-order
    # concat of per-shard list prefixes at exactly this capacity to
    # reproduce the single-host pack bit-identically
    global_list_cap: int = flax.struct.field(pytree_node=False, default=0)

    @property
    def n_shards(self) -> int:
        return self.packed_codes.shape[0]

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_sizes))


class ShardedIvfFlat(flax.struct.PyTreeNode):
    """IVF-Flat index sharded over a mesh axis (raw-vector lists)."""

    centers: jax.Array       # [n_lists, dim] replicated
    packed_data: jax.Array   # [n_dev, n_lists, L, dim] sharded
    packed_ids: jax.Array    # [n_dev, n_lists, L] i32 global ids
    packed_norms: jax.Array  # [n_dev, n_lists, L] f32
    list_sizes: jax.Array    # [n_dev, n_lists] i32
    metric: str = flax.struct.field(pytree_node=False, default="sqeuclidean")
    # see ShardedIvfPq.global_list_cap (parallel.build.assemble_ivf_flat)
    global_list_cap: int = flax.struct.field(pytree_node=False, default=0)

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]


def _warn_dropped(what: str, dropped: jax.Array) -> None:
    """Surface device-side pack overflow on the host (the host packers'
    warn path, ivf_flat._pack_lists:134)."""
    total = int(jnp.sum(dropped))
    if total:
        from raft_tpu.core import logging as _log
        _log.warn("sharded %s build: dropped %d overflow vectors (raise "
                  "list_size_cap_factor)", what, total)


def _pad_shard(x: jax.Array, n_dev: int) -> Tuple[jax.Array, int]:
    n = x.shape[0]
    padded = -(-n // n_dev) * n_dev
    if padded != n:
        x = jnp.pad(x, ((0, padded - n), (0, 0)))
    return x, n


def _coarse_centers(n_lists: int, n_iters: int, seed: int,
                    x: jax.Array, mesh: Mesh, axis: str,
                    spherical: bool) -> jax.Array:
    """Distributed Lloyd coarse fit (the reference trains kmeans_balanced
    per ivf_pq_build.cuh:1618; distributed it becomes the MNMG psum
    pattern). ``x`` must be UNPADDED — dkm.fit pads with zero weights
    itself. Spherical metrics re-normalize the centers."""
    km = KMeansParams(n_clusters=n_lists, max_iter=n_iters, seed=seed)
    centers, _, _ = dkm.fit(km, x, mesh, axis=axis)
    if spherical:
        centers = centers / jnp.sqrt(
            jnp.maximum(jnp.sum(centers**2, -1, keepdims=True), 1e-12))
    return centers


def _gather_trainset(x: jax.Array, mesh: Mesh, axis: str, t: int,
                     seed: int, n_real: int) -> jax.Array:
    """Replicated trainset [n_dev·t, d] sampled uniformly (with
    replacement) from the *global real* rows of the sharded dataset (the
    PQ codebooks' trainset fraction, SURVEY §3.1).

    Every shard draws the SAME global row ids (same key), keeps the ones
    it owns, and a ``psum`` assembles the replicated result — so the zero
    rows `_pad_shard` appends never reach codebook training (even when a
    whole shard is padding), and the sample is uniform over the dataset
    rather than per-shard (which would overweight short shards)."""
    n_dev = mesh.shape[axis]
    total = n_dev * t
    comms = Comms(axis)

    def local(x_shard):
        rank = comms.get_rank()
        shard_n = x_shard.shape[0]
        key = jax.random.PRNGKey(seed)  # identical on every shard
        gidx = jax.random.randint(key, (total,), 0, n_real,
                                  dtype=_ids.id_dtype(n_real))
        local_idx = _ids.local_ids(gidx, rank, shard_n)
        owned = (local_idx >= 0) & (local_idx < shard_n)
        rows = x_shard[jnp.clip(local_idx, 0, shard_n - 1)]
        contrib = jnp.where(owned[:, None], rows, 0.0)
        return comms.allreduce(contrib)

    fn = shard_map(local, mesh=mesh, in_specs=(P(axis, None),),
                   out_specs=P(), check_vma=False)
    return fn(x)


def _label_replicated(trainset: jax.Array, centers: jax.Array,
                      mesh: Mesh) -> jax.Array:
    """Nearest-center labels of a mesh-replicated ``trainset``. Every
    device labels its own copy inside ``shard_map``: on a TPU the argmin
    is a Pallas kernel, and XLA cannot partition a Mosaic call across a
    mesh by itself."""
    fn = shard_map(lambda t, c: fused_l2_nn_argmin(t, c)[1], mesh=mesh,
                   in_specs=(P(), P()), out_specs=P(), check_vma=False)
    return fn(trainset, centers)


# Cross-shard candidate merges route through parallel/merge.py — the
# one dispatch point shared with parallel/knn.py (allgather-and-select
# vs the ring reduce-scatter-of-top-k tier; reference:
# knn_merge_parts.cuh). All merge traffic rides the Comms facade so it
# lands in the ``comms.ops``/``comms.bytes`` counters per axis.


# ---------------------------------------------------------------------------
# fused scan-in-ring tier (ROADMAP item 5): per-shard LUT scan folded
# into the ring exchange — one persistent kernel from packed codes to
# the merged top-k; the per-shard [m, k] candidate table never exists
# ---------------------------------------------------------------------------

def _ring_fused_wanted(index: "ShardedIvfPq", m: int, k: int,
                       n_probes: int, n_dev: int, whole_mesh: bool,
                       merge: str, mt: DistanceType, lut_dtype: str,
                       scan_select: str,
                       filtered: bool = False) -> Tuple[bool, str]:
    """Dispatch for the fused scan-in-ring tier. Returns
    ``(take_it, decline_reason)`` — reason is non-empty only when the
    tier was WANTED (env force, or auto on an eligible ring setup) but
    a capability check declined it; those land in
    ``parallel.merge.fallback{reason=...}`` so "why isn't the sharded
    scan fused?" is one counter query.

    ``RAFT_TPU_RING_FUSED`` = auto | on | off: auto takes the tier
    exactly where the ring KERNEL would have carried the merge (TPU,
    whole-mesh axis, ring-winning shape) — the fused kernel is the same
    exchange with the scan moved inside; "on" forces it (interpret mode
    off-TPU — tests), "off" never. The tier declines (fallback to the
    unfused scan + merge path, preserving every existing dispatch rung,
    including the int64-id ppermute decline):

    - ``scan_select``: the fused kernel carries the LUT-bin tier's
      recall-targeted selection semantics, so it only serves searches
      the single-chip dispatch would route there anyway — an explicit
      ``scan_select="pallas"``, or ``"approx"`` at the oversampled
      auto-upgrade shape. The default ``"exact"`` keeps exact-selection
      semantics on the unfused path, even under env force;
    - ``id_width``: int64 id tables — the kernel is int32-only;
    - ``metric``: cosine (the fused epilogue serves l2/ip keys);
    - ``kernel_ineligible``: unsupported packed layout, k past the
      merge budget, VMEM budget, or a union-segment table past
      ``RING_FUSED_MAX_SEGS``;
    - ``latency_bound``: shapes where auto mode keeps the single
      allgather (``ring_auto_wanted``).

    ``filtered`` admits the per-shard filter-byte stream: the kernel's
    VMEM model grows the filter slots + unpack selection matrix
    (``ring_lut_scan_kernel_ok``) and the HBM transient — the shard's
    ``[n_lists, ceil(L/8)]`` packed byte rows — must pass
    ``ivf_common.filtered_scan_mem_ok`` (``mem_guard`` decline).
    """
    from raft_tpu.obs import spans as _obs_spans
    from raft_tpu.ops import pallas_kernels as _pk

    force = _obs_spans.env_tristate("RAFT_TPU_RING_FUSED")
    if force == "off" or merge == "allgather":
        return False, ""
    if force != "on" and not (_pk._on_tpu() and whole_mesh):
        return False, ""
    if not (scan_select == "pallas"
            or (scan_select == "approx"
                and (n_probes >= 64 or k >= 400))):
        # never swap exact-selection semantics for the bin tier's —
        # mirror of the single-chip LUT-tier routing
        return False, "scan_select"
    if mt not in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                  DistanceType.InnerProduct):
        return False, "metric"
    if jnp.dtype(index.packed_ids.dtype).itemsize >= 8:
        return False, "id_width"
    if force != "on" and not _merge.ring_auto_wanted(m, k, n_dev):
        return False, "latency_bound"
    mc = _pk.ring_chunk_rows(m, n_dev)
    NS = min(mc * n_probes, index.n_lists)
    # nb from pq geometry, Wb from the stored layout — the same pair
    # ring_lut_scan_merge derives, so the admission check and the
    # kernel agree on lane-folded layouts (Wb > nb) instead of only on
    # the unfolded sharded build where the two coincide
    nb = (index.pq_dim * index.pq_bits + 7) // 8
    Wb = index.packed_codes.shape[3]
    ok = _pk.ring_lut_scan_kernel_ok(
        index.pq_dim, 1 << index.pq_bits,
        index.codebooks.shape[2], nb, Wb, mc, NS, k, n_dev,
        index.centers_rot.shape[1], lut_dtype=lut_dtype,
        filtered=filtered)
    if not ok:
        return False, "kernel_ineligible"
    if filtered and not ic.filtered_scan_mem_ok(
            index.n_lists, index.packed_ids.shape[2]):
        return False, "mem_guard"
    return True, ""


def _search_fused_ring(index: "ShardedIvfPq", q: jax.Array, k: int,
                       n_probes: int, mesh: Mesh, axis: str,
                       lut_dtype: str, mt: DistanceType,
                       filter_bits=None
                       ) -> Tuple[jax.Array, jax.Array]:
    """The fused scan-in-ring search: probes + chunk unions + one
    persistent Pallas kernel per shard (``ring_lut_scan_merge``), then
    the LUT-key → metric epilogue. Results are query-sharded like the
    ring merge tier's.

    ``filter_bits`` (replicated, GLOBAL row ids): each shard composes
    the global bitset with its own global-id table — the per-shard
    bitset slice — into the packed per-candidate byte rows the ring
    kernel streams beside the codes (``sample_filter.list_filter_bytes``
    over ``packed_ids[shard]``, whose global ids bake in the shard
    offset), so filtered pod-scale search rides the ring kernel too."""
    from raft_tpu.obs import spans as _obs_spans
    from raft_tpu.ops import pallas_kernels as _pk

    m = q.shape[0]
    n_dev = index.n_shards
    mc = _pk.ring_chunk_rows(m, n_dev)
    mq = mc * n_dev
    ip_like = mt == DistanceType.InnerProduct
    NS = min(mc * n_probes, index.n_lists)
    L = index.packed_codes.shape[2]
    qp = jnp.pad(q, ((0, mq - m), (0, 0))) if mq > m else q
    comms = Comms(axis)
    interpret = not _pk._on_tpu()

    def body(codes, ids, norms, sizes, qp, centers, centers_rot,
             rotation, codebooks, *fb):
        local = _pq.IvfPqIndex(
            centers=centers, centers_rot=centers_rot, rotation=rotation,
            codebooks=codebooks, packed_codes=codes[0],
            packed_ids=ids[0], packed_norms=norms[0],
            list_sizes=sizes[0], metric=index.metric,
            pq_bits=index.pq_bits, pq_dim_static=index.pq_dim)
        # probes on replicated operands: identical on every shard
        _, probes = _pq._coarse_probes(local, qp, n_probes, ip_like)
        q_rot = jnp.matmul(qp, rotation.T, precision=get_precision())
        lists, ind = _chunk_unions(
            probes.reshape(n_dev, mc, n_probes), NS)
        qv = q_rot.reshape(n_dev, mc, q_rot.shape[1])
        fbytes = None
        if fb:
            from raft_tpu.neighbors import sample_filter as _sf

            # the per-shard bitset slice: this shard's id table carries
            # GLOBAL ids (the shard offset baked in at build), so one
            # passes() gather over it composes the replicated global
            # bitset with the global→local remap — re-packed to the
            # per-list byte rows the ring kernel streams per code tile
            fbytes = _sf.list_filter_bytes(fb[0], ids[0])
        # the kernel's remote DMAs bypass lax — attribute the hop
        # traffic through the facade at trace time, the same [mc, k]
        # logical block per hop as the plain ring merge (the fusion
        # moves compute, not bytes)
        comms.count_ring_topk(
            n_dev - 1,
            jax.ShapeDtypeStruct((mc, k), jnp.float32),
            jax.ShapeDtypeStruct((mc, k), jnp.int32))
        kv, ki = _pk.ring_lut_scan_merge(
            lists, ind, qv, codes[0], ids[0], norms[0], centers_rot,
            codebooks, k, "ip" if ip_like else "l2",
            pq_bits=index.pq_bits, pq_dim=index.pq_dim, L=L,
            axis_name=axis, n_dev=n_dev, lut_dtype=lut_dtype,
            filter_bytes=fbytes, interpret=interpret)
        return kv[:, :k], ki[:, :k]

    in_specs = [P(axis, None, None, None), P(axis, None, None),
                P(axis, None, None), P(axis, None), P(),
                P(), P(), P(), P()]
    operands = [index.packed_codes, index.packed_ids, index.packed_norms,
                index.list_sizes, qp, index.centers, index.centers_rot,
                index.rotation, index.codebooks]
    if filter_bits is not None:
        in_specs.append(P())   # the global bitset rides replicated
        operands.append(filter_bits)
    out_spec = P(axis, None)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(out_spec, out_spec),
        check_vma=False)
    rv, ri = fn(*operands)
    rv, ri = rv[:m], ri[:m]
    # LUT-key → metric epilogue (the _finish_candidates conventions)
    if ip_like:
        dists = jnp.where(ri < 0, -jnp.inf, -rv)
    else:
        q_sq = jnp.sum(jnp.matmul(q, index.rotation.T,
                                   precision=get_precision()) ** 2, axis=1)
        dists = jnp.maximum(rv + q_sq[:, None], 0.0)
        if mt == DistanceType.L2SqrtExpanded:
            dists = jnp.sqrt(dists)
        dists = jnp.where(ri < 0, jnp.inf, dists)
    return dists, ri


def _chunk_unions(pc: jax.Array, NS: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Per ring chunk, the padded union of probed lists and the
    per-(list, query) membership indicator the fused kernel masks with.

    ``pc [n_dev, mc, n_probes]`` i32 → (``lists [n_dev, NS]`` i32, −1
    pad; ``ind [n_dev, NS, mc]`` f32 0/1). Sort + first-occurrence +
    one bounded scatter — ``NS = min(mc·n_probes, n_lists)`` bounds the
    distinct count by construction, so the scatter never drops a real
    list."""
    def one(p):
        flat = jnp.sort(p.reshape(-1).astype(jnp.int32))
        first = jnp.concatenate(
            [jnp.ones((1,), bool), flat[1:] != flat[:-1]])
        rank = jnp.cumsum(first) - 1
        lists = jnp.full((NS,), -1, jnp.int32)
        lists = lists.at[jnp.where(first, rank, NS)].set(flat,
                                                         mode="drop")
        ind = jnp.any(p[None, :, :] == lists[:, None, None], axis=2)
        ind = ind & (lists >= 0)[:, None]
        return lists, ind.astype(jnp.float32)

    return jax.vmap(one)(pc)


def build_ivf_pq(params: _pq.IndexParams, dataset: jax.Array, mesh: Mesh,
                 axis: str = "shard") -> ShardedIvfPq:
    """Distributed IVF-PQ build over a row-sharded dataset.

    reference: the raft-dask sharded-index pattern (each worker an
    ivf_pq::build over its shard) with the coarse quantizer trained
    globally (MNMG kmeans) instead of per-shard — sharper lists than the
    reference's per-worker quantizers at zero extra comms beyond psum.
    """
    mt = resolve_metric(params.metric)
    expects(params.codebook_kind == "per_subspace",
            "distributed build supports per_subspace codebooks")
    x = jnp.asarray(dataset, jnp.float32)
    n, dim = x.shape
    n_dev = mesh.shape[axis]
    spherical = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)
    if mt == DistanceType.CosineExpanded:
        x = x / jnp.sqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-12))

    pq_dim = params.pq_dim or _pq._default_pq_dim(dim)
    pq_len = -(-dim // pq_dim)
    rot_dim = pq_dim * pq_len
    K = 1 << params.pq_bits

    # 1. global coarse centers (ONE psum Lloyd over the sharded rows;
    #    dkm.fit zero-weights its own padding)
    centers = _coarse_centers(params.n_lists, params.kmeans_n_iters,
                              params.seed, x, mesh, axis, spherical)

    x, n_real = _pad_shard(x, n_dev)
    shard_n = x.shape[0] // n_dev

    # 2. rotation + codebooks on a replicated cross-shard subsample sized
    #    by kmeans_trainset_fraction (parity with the single-device build)
    key = jax.random.PRNGKey(params.seed)
    rotation = _pq.make_rotation_matrix(jax.random.fold_in(key, 1),
                                        rot_dim, dim)
    centers_rot = jnp.matmul(centers, rotation.T, precision=get_precision())
    t = min(shard_n,
            max(int(shard_n * params.kmeans_trainset_fraction),
                -(-4 * K // n_dev), 256))
    expects(t * n_dev >= K,
            "trainset too small for pq_bits=%d: %d < %d codebook entries",
            params.pq_bits, t * n_dev, K)
    trainset = _gather_trainset(x, mesh, axis, t, params.seed, n_real)
    tr_labels = _label_replicated(trainset, centers, mesh)
    tr_res = (jnp.matmul(trainset, rotation.T, precision=get_precision())
              - centers_rot[tr_labels])
    sub = jnp.transpose(tr_res.reshape(-1, pq_dim, pq_len), (1, 0, 2))
    codebooks = _pq._vmapped_lloyd(sub, K, params.kmeans_n_iters,
                                   jax.random.fold_in(key, 2))

    # 3. per-shard encode + device-side pack (global ids baked in)
    avg = max(1, shard_n // params.n_lists)
    L = max(8, -(-int(avg * params.list_size_cap_factor) // 8) * 8)
    n_lists = params.n_lists
    comms = Comms(axis)

    def encode_pack(x_blk, centers, centers_rot, rotation, codebooks):
        xs = x_blk
        rank = comms.get_rank()
        # global ids in the policy dtype of the POD row count (core.ids):
        # rank·shard_n overflows int32 past 2³¹ total rows
        gid = _ids.global_ids(rank, shard_n, _ids.make_ids(shard_n),
                              n_total=n_dev * shard_n)
        _, labels = fused_l2_nn_argmin(xs, centers)
        labels = jnp.where(gid < n_real, labels, n_lists)  # drop pad rows
        safe = jnp.clip(labels, 0, n_lists - 1)
        x_rot = jnp.matmul(xs, rotation.T, precision=get_precision())
        codes = _pq._encode_rows(x_rot, centers_rot, safe, codebooks)
        decoded = _pq._decode_codes(codes, codebooks)
        recon = centers_rot[safe] + decoded
        norms = jnp.sum(recon * recon, axis=1)
        codes_p = _pq.pack_bits(codes, params.pq_bits)  # n-bit device pack
        (pcodes, pnorms), ids, sizes, dropped, _ = ic.pack_lists(
            (codes_p, norms), labels, gid, n_lists, L,
            (jnp.uint8(0), jnp.float32(0)))
        return pcodes[None], ids[None], pnorms[None], sizes[None], dropped[None]

    fn = shard_map(
        encode_pack, mesh=mesh,
        in_specs=(P(axis, None), P(), P(), P(), P()),
        out_specs=(P(axis, None, None, None), P(axis, None, None),
                   P(axis, None, None), P(axis, None), P(axis)),
        check_vma=False)
    pcodes, pids, pnorms, sizes, dropped = fn(x, centers, centers_rot,
                                              rotation, codebooks)
    _warn_dropped("ivf_pq", dropped)
    return ShardedIvfPq(
        centers=centers, centers_rot=centers_rot, rotation=rotation,
        codebooks=codebooks, packed_codes=pcodes, packed_ids=pids,
        packed_norms=pnorms, list_sizes=sizes, metric=mt.value,
        pq_bits=params.pq_bits, pq_dim=pq_dim, shard_rows=shard_n)


def search_ivf_pq(params: _pq.SearchParams, index: ShardedIvfPq,
                  queries: jax.Array, k: int, mesh: Mesh,
                  axis: Union[str, Sequence[str]] = "shard", dataset=None,
                  merge: str = "auto",
                  filter_bitset=None) -> Tuple[jax.Array, jax.Array]:
    """Sharded IVF-PQ search: per-shard list scan + cross-shard top-k
    merge (reference: per-worker search + knn_merge_parts.cuh). Queries
    are replicated; returns (distances [m, k], global ids [m, k]) —
    replicated under the allgather merge tier, query-sharded under the
    ring tier (``merge`` = auto | allgather | ring, see
    ``parallel.merge``).

    With ``params.refine="f32_regen"`` and ``dataset`` (the build
    dataset, row-sharded over the mesh) this is the end-to-end fused
    pipeline per shard: the oversampled scan rides whatever tier
    ``ivf_pq.search`` picks (incl. the Pallas LUT-scan kernel), the
    exact re-rank rides the gather-refine dispatch tier against the
    shard's own rows, and only each shard's k refined survivors enter
    the merge — BASELINE config 5's shape (sharded IVF-PQ, SIFT-1B on
    v5e-64) end to end.

    ``filter_bitset`` (packed uint32 words over GLOBAL row ids,
    replicated): every per-shard tier composes it with the shard's
    global-id tables — the fused ring kernel streams the per-shard
    byte slice beside the codes, the unfused scan and the refined
    pipeline's oversampled scan mask in their own tiers — so filtered
    pod-scale search stays on whatever fast path the unfiltered shape
    would ride."""
    mt = resolve_metric(index.metric)
    select_min = SELECT_MIN[mt]
    n_probes = min(params.n_probes, index.n_lists)
    q = jnp.asarray(queries, jnp.float32)
    # same entry contract as the single-chip search: validate queries
    # up front (not deep inside shard_map) and expose the PR-7 fault
    # point so chaos plans cover the sharded tier too
    expects(q.ndim == 2 and q.shape[1] == index.dim,
            "queries must be [m, %d]", index.dim)
    _faults.faultpoint("ivf_pq.search")
    m = q.shape[0]
    n_dev = index.n_shards
    ax_dev, whole_mesh, hier_axes = _merge.resolve_exchange(mesh, axis)
    expects(n_dev == ax_dev,
            "index sharded over %d devices, mesh axis has %d",
            n_dev, ax_dev)
    refined = params.refine != "none"
    filtered = filter_bitset is not None
    if params.lut_dtype == "auto" and not refined:
        # direct sharded calls resolve the fp8-default policy here (the
        # neighbors entry resolves before dispatching to this tier).
        # Refined searches stay "auto" so the per-shard oversampled
        # scan resolves against its ACTUAL selection width k_cand —
        # the slack the fp8 floor is defined over. A filter's
        # selectivity discounts the slack (surviving candidates only)
        params = dataclasses.replace(
            params, lut_dtype=_pq.resolve_lut_dtype(
                "auto", n_probes, k,
                selectivity=_pq._filter_selectivity(filter_bitset)))
    if not refined:
        from raft_tpu.obs import spans as _obs_spans

        fused, fused_reason = _ring_fused_wanted(
            index, m, k, n_probes, n_dev,
            whole_mesh=whole_mesh, merge=merge, mt=mt,
            lut_dtype=params.lut_dtype, scan_select=params.scan_select,
            filtered=filtered)
        if fused:
            # codes → merged top-k in one persistent kernel: the scan
            # IS the merge's compute phase, no per-shard candidate
            # table, no separate merge dispatch
            _obs_spans.count_dispatch("parallel.merge", "ring_fused_scan")
            _pq._count_scan_dispatch("ring_lut_fused", filtered=filtered)
            rv, ri = _search_fused_ring(index, q, k, n_probes, mesh,
                                        axis, params.lut_dtype, mt,
                                        filter_bits=filter_bitset)
            return rv, ri
        if fused_reason:
            _obs_spans.count_fallback("parallel.merge", fused_reason)
    tier, impl = _merge.merge_tier(
        n_dev, m, k, explicit=merge,
        whole_mesh=whole_mesh, hier_axes=hier_axes)
    comms = Comms(axis)
    if refined:
        from raft_tpu.neighbors import refine as _refine

        expects(dataset is not None,
                "refine=%r needs search(..., dataset=...): the sharded "
                "rows to re-rank against (the build dataset)",
                params.refine)
        xd = jnp.asarray(dataset, jnp.float32)
        expects(xd.ndim == 2 and xd.shape[1] == index.dim,
                "refine dataset shape %s does not match the index dim %d",
                tuple(xd.shape), index.dim)
        if mt == DistanceType.CosineExpanded:
            xd = xd / jnp.sqrt(
                jnp.maximum(jnp.sum(xd * xd, -1, keepdims=True), 1e-12))
        xd, _ = _pad_shard(xd, n_dev)
        shard_n = xd.shape[0] // n_dev
        # the gid → local-row remap below is only correct against the
        # BUILD dataset's shard geometry — a row-count mismatch would
        # refine against the wrong vectors silently (JAX clamps
        # out-of-range gathers)
        expects(index.shard_rows == 0 or shard_n == index.shard_rows,
                "refine dataset has %d rows/shard but the index was "
                "built with %d — pass the build dataset",
                shard_n, index.shard_rows)
        k_cand = max(k, int(round(k * params.refine_ratio)))
        scan_params = dataclasses.replace(params, refine="none")

    def local_search(codes, ids, norms, sizes, q,
                     centers, centers_rot, rotation, codebooks, *rest):
        rest = list(rest)
        ds = rest.pop(0) if refined else None
        fb = rest.pop(0) if filtered else None
        local = _pq.IvfPqIndex(
            centers=centers, centers_rot=centers_rot, rotation=rotation,
            codebooks=codebooks, packed_codes=codes[0], packed_ids=ids[0],
            packed_norms=norms[0], list_sizes=sizes[0], metric=index.metric,
            pq_bits=index.pq_bits, pq_dim_static=index.pq_dim)
        if refined:
            # per-shard fused pipeline: oversampled scan through the
            # full single-chip dispatch stack (LUT-scan tier included —
            # a filter rides it as the streamed per-candidate mask: the
            # shard's id tables are global, so the replicated bitset
            # composes directly), exact re-rank against this shard's
            # own rows (ids are global with the shard offset baked in
            # at build)
            _, i0 = _pq.search(local, q, k_cand, scan_params,
                               filter_bitset=fb)
            rank = comms.get_rank()
            # global↔local remap through the one id-dtype policy
            # (core.ids): the offset math overflows int32 past 2³¹ pod
            # rows, and the incoming id width is never narrowed. i0 is
            # already filter-clean — the refine re-rank needs no filter
            li = _ids.local_ids(i0, rank, shard_n)
            vals, lids = _refine.refine(ds, q, li, k,
                                        metric=index.metric)
            gids = _ids.global_ids(rank, shard_n, lids,
                                   n_total=n_dev * shard_n)
        else:
            vals, gids = _pq._search_impl(local, q, k, n_probes,
                                          params.query_tile,
                                          filter_bits=fb,
                                          lut_dtype=params.lut_dtype)
        return _merge.merge_topk(vals, gids, axis, m, k, n_dev,
                                 select_min, tier=tier, impl=impl)

    in_specs = [P(axis, None, None, None), P(axis, None, None),
                P(axis, None, None), P(axis, None), P(),
                P(), P(), P(), P()]
    operands = [index.packed_codes, index.packed_ids, index.packed_norms,
                index.list_sizes, q, index.centers, index.centers_rot,
                index.rotation, index.codebooks]
    if refined:
        in_specs.append(P(axis, None))
        operands.append(xd)
    if filtered:
        in_specs.append(P())   # global bitset, replicated
        operands.append(filter_bitset)
    out_spec = _merge.merge_out_spec(tier, axis)
    fn = shard_map(
        local_search, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(out_spec, out_spec),
        check_vma=False)
    rv, ri = fn(*operands)
    return rv[:m], ri[:m]


def build_ivf_flat(params: _flat.IndexParams, dataset: jax.Array, mesh: Mesh,
                   axis: str = "shard") -> ShardedIvfFlat:
    """Distributed IVF-Flat build: global coarse centers (psum Lloyd) +
    per-shard device-side raw-vector packing."""
    mt = resolve_metric(params.metric)
    x = jnp.asarray(dataset, jnp.float32)
    n, dim = x.shape
    n_dev = mesh.shape[axis]
    spherical = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)
    if mt == DistanceType.CosineExpanded:
        x = x / jnp.sqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-12))
    n_lists = params.n_lists

    centers = _coarse_centers(n_lists, params.kmeans_n_iters,
                              params.seed, x, mesh, axis, spherical)

    x, n_real = _pad_shard(x, n_dev)
    shard_n = x.shape[0] // n_dev

    avg = max(1, shard_n // n_lists)
    L = max(8, -(-int(avg * params.list_size_cap_factor) // 8) * 8)
    comms = Comms(axis)

    def assign_pack(x_blk, centers):
        rank = comms.get_rank()
        gid = _ids.global_ids(rank, shard_n, _ids.make_ids(shard_n),
                              n_total=n_dev * shard_n)
        _, labels = fused_l2_nn_argmin(x_blk, centers)
        labels = jnp.where(gid < n_real, labels, n_lists)
        norms = jnp.sum(x_blk * x_blk, axis=1)
        (pdata, pnorms), ids, sizes, dropped, _ = ic.pack_lists(
            (x_blk, norms), labels, gid, n_lists, L,
            (jnp.float32(0), jnp.float32(0)))
        return pdata[None], ids[None], pnorms[None], sizes[None], dropped[None]

    fn = shard_map(
        assign_pack, mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=(P(axis, None, None, None), P(axis, None, None),
                   P(axis, None, None), P(axis, None), P(axis)),
        check_vma=False)
    pdata, pids, pnorms, sizes, dropped = fn(x, centers)
    _warn_dropped("ivf_flat", dropped)
    return ShardedIvfFlat(centers=centers, packed_data=pdata,
                          packed_ids=pids, packed_norms=pnorms,
                          list_sizes=sizes, metric=mt.value)


def search_ivf_flat(params: _flat.SearchParams, index: ShardedIvfFlat,
                    queries: jax.Array, k: int, mesh: Mesh,
                    axis: Union[str, Sequence[str]] = "shard",
                    merge: str = "auto",
                    filter_bitset=None) -> Tuple[jax.Array, jax.Array]:
    """Sharded IVF-Flat search: per-shard scan + cross-shard merge
    through the shared tier (``merge`` = auto | allgather | ring).

    ``filter_bitset`` (packed words over GLOBAL row ids, replicated)
    masks each shard's scan through the same per-shard global-id
    composition as the PQ tier."""
    mt = resolve_metric(index.metric)
    select_min = SELECT_MIN[mt]
    n_probes = min(params.n_probes, index.n_lists)
    q = jnp.asarray(queries, jnp.float32)
    expects(q.ndim == 2 and q.shape[1] == index.dim,
            "queries must be [m, %d]", index.dim)
    _faults.faultpoint("ivf_flat.search")
    m = q.shape[0]
    n_dev = index.packed_data.shape[0]
    ax_dev, whole_mesh, hier_axes = _merge.resolve_exchange(mesh, axis)
    expects(n_dev == ax_dev,
            "index sharded over %d devices, mesh axis has %d",
            n_dev, ax_dev)
    tier, impl = _merge.merge_tier(
        n_dev, m, k, explicit=merge,
        whole_mesh=whole_mesh, hier_axes=hier_axes)

    def local_search(data, ids, norms, sizes, q, centers, *fb):
        local = _flat.IvfFlatIndex(
            centers=centers, packed_data=data[0], packed_ids=ids[0],
            packed_norms=norms[0], list_sizes=sizes[0], metric=index.metric)
        vals, gids = _flat._search_impl(local, q, k, n_probes,
                                        params.query_tile,
                                        filter_bits=fb[0] if fb else None)
        return _merge.merge_topk(vals, gids, axis, m, k, n_dev,
                                 select_min, tier=tier, impl=impl)

    in_specs = [P(axis, None, None, None), P(axis, None, None),
                P(axis, None, None), P(axis, None), P(), P()]
    operands = [index.packed_data, index.packed_ids, index.packed_norms,
                index.list_sizes, q, index.centers]
    if filter_bitset is not None:
        in_specs.append(P())   # global bitset, replicated
        operands.append(filter_bitset)
    out_spec = _merge.merge_out_spec(tier, axis)
    fn = shard_map(
        local_search, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(out_spec, out_spec),
        check_vma=False)
    rv, ri = fn(*operands)
    return rv[:m], ri[:m]
