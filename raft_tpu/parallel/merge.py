"""Cross-shard top-k merge tiers — the single dispatch point behind every
distributed candidate merge (sharded kNN and both sharded IVF searches).

Two tiers return the same global top-k, with very different traffic
(reference: ``knn_merge_parts.cuh`` merged over NCCL in raft-dask):

- **allgather**: every device gathers the full ``[n_dev, m, k]``
  candidate tables over ICI and selects locally — O(n_dev·m·k) bytes
  materialized per device, the original merge. Result is replicated.
- **ring**: reduce-scatter-of-top-k. The query axis splits into n_dev
  chunks; each chunk's partial top-k travels the ring for n_dev−1 hops,
  merged against each device's local candidates on the way, landing
  fully merged at its owner — only the surviving ``[m/n_dev, k]`` block
  ever crosses a link, O(m·k) bytes per device total. Result is
  query-sharded (``P(axis)`` out-specs; callers slice the assembled
  array back to ``[m, k]``). On TPU the hops are the Pallas
  ``ring_topk_merge`` kernel's async remote DMAs; elsewhere (the
  8-device CPU CI mesh) and on sub-axis rings of a multi-axis mesh an
  identical-schedule ``ppermute`` fallback keeps semantics and
  ``comms.ops/bytes{op=ring_topk}`` accounting bit-for-bit comparable.

- **hier** (ISSUE 19): the two-level cross-POD merge for 2-D
  ``(outer=dcn, inner=ici)`` meshes. The inner (ICI) stage is the ring
  tier per pod, exactly as today — the Pallas persistent kernel where
  eligible (:func:`raft_tpu.ops.pallas_kernels.ring_topk_inner_ok`),
  the ppermute schedule elsewhere — leaving each device its pod's
  fully-merged ``[mc, k]`` survivor block. Then only those k survivors
  — never raw candidates — cross DCN once: each device owns a
  ``1/n_outer`` sub-chunk of its pod block and allgathers every pod's
  survivors FOR ITS OWNED ROWS over the outer axis (the sparse
  survivor exchange: one collective, no serial DCN hop chain), selects
  k of ``n_outer·k`` locally. DCN traffic is the k-survivor model —
  ``n_outer · mc_d · k`` entries per device, O(k·pods) — independent
  of how many devices scanned, vs the flat ring's whole
  ``(n_dev−1)·mc·k`` stream pacing on the slow links. Result is
  query-sharded over (inner, outer); callers slice ``[:m]``.

``RAFT_TPU_RING_TOPK`` (auto | on | off, :func:`raft_tpu.obs.env_tristate`)
picks the flat tier; ``RAFT_TPU_HIER_MERGE`` (auto | on | off) gates the
hier tier, auto-on when the caller's 2-D mesh has a DCN-labeled outer
axis (:func:`raft_tpu.parallel.mesh.is_dcn_axis`); explicit ``merge=``
arguments on the search entries override both. Every decision lands in
``parallel.merge.dispatch{impl=...}``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.lax import axis_size as _axis_size
from raft_tpu.core.errors import expects
from raft_tpu.core import ids as _ids
from raft_tpu.matrix import select_k as _select_k
from raft_tpu.obs import spans as _obs_spans
from raft_tpu.ops import pallas_kernels as _pk
from raft_tpu.parallel.comms import Comms
from raft_tpu.parallel.mesh import is_dcn_axis

MERGE_TIERS = ("allgather", "ring", "hier")

# (outer_axis, inner_axis, n_outer, n_inner) — the hier tier's static
# topology summary, built by the search entries from their mesh + a
# 2-tuple ``axis`` argument (outer DCN-labeled). None = 1-D exchange.
HierAxes = Tuple[str, str, int, int]


def resolve_exchange(mesh, axis: Union[str, Sequence[str]]
                     ) -> Tuple[int, bool, Optional[HierAxes]]:
    """Normalize a search entry's ``axis`` argument — one mesh axis name
    or a 2-tuple ``(outer, inner)`` — against its mesh. Returns
    ``(n_dev, whole_mesh, hier_axes)``: device count of the exchange,
    whether it spans the whole mesh as ONE named axis (the flat ring
    kernel's logical-id addressing requirement), and the hier topology
    summary when the tuple's outer axis is DCN-labeled (None otherwise —
    flat tiers still serve DCN-unlabeled tuples, they just never
    auto-escalate to hier)."""
    if isinstance(axis, str):
        n_dev = mesh.shape[axis]
        return n_dev, n_dev == mesh.devices.size, None
    names = tuple(axis)
    expects(len(names) == 2,
            "axis must be one mesh axis name or a 2-tuple "
            "(outer, inner), got %r", axis)
    outer, inner = names
    n_outer, n_inner = mesh.shape[outer], mesh.shape[inner]
    hier = (outer, inner, n_outer, n_inner) if is_dcn_axis(outer) else None
    return n_outer * n_inner, False, hier


def ring_auto_wanted(m: int, k: int, n_dev: int) -> bool:
    """Auto-mode shape gate: take the ring only where it actually wins.
    The ring ships (n_dev−1) sublane-padded ``[mc, k]`` blocks over
    n_dev−1 SERIAL hops vs the allgather's one collective of
    n_dev·[m, k]; for tiny query batches the mc=8 row padding makes the
    ring ship MORE bytes and the hop chain is pure added latency.
    Require the ring's counted bytes to be ≤ half the allgather's (the
    same ≥2× bar the scaling CI asserts) before auto prefers it."""
    mc = _pk.ring_chunk_rows(m, n_dev)
    return 2 * (n_dev - 1) * mc <= n_dev * m


def hier_chunk_rows(m: int, n_inner: int, n_outer: int) -> int:
    """Per-device query-chunk rows of the hier tier's inner (per-pod)
    ring: the flat ring's sublane-padded chunk for ``n_inner`` devices,
    padded up so the outer survivor exchange splits it into ``n_outer``
    even sub-chunks."""
    mc = _pk.ring_chunk_rows(m, n_inner)
    return -(-mc // n_outer) * n_outer


def merge_tier(n_dev: int, m: int, k: int,
               explicit: Optional[str] = None,
               whole_mesh: bool = True,
               hier_axes: Optional[HierAxes] = None) -> Tuple[str, str]:
    """Pick the merge tier + implementation for one sharded search call.

    ``explicit`` (a search entry's ``merge=`` argument, "auto" = defer)
    overrides the ``RAFT_TPU_RING_TOPK`` tri-state; auto mode takes the
    ring tier on TPU when the kernel can serve the shape AND the shape
    is bandwidth-bound enough to win (:func:`ring_auto_wanted` —
    small/latency-bound batches keep the single allgather). The kernel
    addresses neighbors by logical device id, so it needs the exchange
    axis to be the ``whole_mesh``; sub-axis rings and non-TPU backends
    ride the ppermute fallback. Returns ``(tier, impl)`` with impl ∈
    {allgather, ring_kernel, ring_ppermute, hier}; counted per decision
    under ``parallel.merge.dispatch{impl=...}``.

    ``hier_axes`` (set by a search entry called with a 2-tuple
    ``axis`` whose outer axis is DCN-labeled) enables the hier tier:
    taken on ``merge="hier"`` or, under auto, whenever present unless
    ``RAFT_TPU_HIER_MERGE=off`` — a topology honest enough to name its
    slow axis should never flat-merge across it by default."""
    hier_force = _obs_spans.env_tristate("RAFT_TPU_HIER_MERGE")
    if explicit == "hier":
        expects(hier_axes is not None,
                "merge='hier' needs a 2-D (outer, inner) exchange: call "
                "the search with axis=(dcn_axis, ici_axis) over a "
                "hier_mesh-shaped mesh (DCN-labeled outer axis)")
    if hier_axes is not None and (
            explicit == "hier"
            or (explicit in (None, "auto") and hier_force != "off")):
        _obs_spans.count_dispatch("parallel.merge", "hier")
        return "hier", "hier"
    if hier_axes is None and hier_force == "on" \
            and explicit in (None, "auto"):
        # env asked for hier but the exchange is 1-D — fall through to
        # the flat tiers, visibly
        _obs_spans.count_fallback("parallel.merge", "no_hier_axes")
    force = _obs_spans.env_tristate("RAFT_TPU_RING_TOPK")
    kernel_ok = (_pk._on_tpu() and whole_mesh
                 and _pk.ring_topk_kernel_ok(m, k, n_dev))
    if explicit is not None and explicit != "auto":
        expects(explicit in MERGE_TIERS,
                "unknown merge tier %r (supported: %s)", explicit,
                "/".join(MERGE_TIERS))
        tier = explicit
    elif force == "off":
        tier = "allgather"
    elif force == "on":
        tier = "ring"
    else:
        tier = ("ring" if kernel_ok and ring_auto_wanted(m, k, n_dev)
                else "allgather")
        if _pk._on_tpu() and tier == "allgather" and n_dev > 1:
            _obs_spans.count_fallback(
                "parallel.merge",
                "latency_bound" if kernel_ok else "kernel_ineligible")
    impl = "allgather"
    if tier == "ring":
        impl = "ring_kernel" if kernel_ok else "ring_ppermute"
    _obs_spans.count_dispatch("parallel.merge", impl)
    return tier, impl


def merge_out_spec(tier: str, axis: Union[str, Sequence[str]]) -> P:
    """shard_map out-spec for one merged output: the allgather tier
    replicates, the ring tier leaves results query-sharded, the hier
    tier leaves them sharded over (inner, outer) — device (d, i) owns
    sub-chunk d of inner chunk i, so the assembled padded query order
    is exactly the flat one and callers still slice ``[:m]``."""
    if tier == "hier":
        outer, inner = axis
        return P((inner, outer), None)
    return P() if tier == "allgather" else P(axis, None)


def merged_rows(tier: str, m: int, n_dev: int, n_outer: int = 1) -> int:
    """Global row count of the assembled merge result (the ring tier
    pads the query axis to n_dev chunks of sublane-tiled rows; pad rows
    sit at the END, so callers slice ``[:m]``). For the hier tier pass
    ``n_dev`` = the INNER axis size and ``n_outer`` = the pod count."""
    if tier == "allgather":
        return m
    if tier == "hier":
        return hier_chunk_rows(m, n_dev, n_outer) * n_dev
    return _pk.ring_chunk_rows(m, n_dev) * n_dev


def _merge_allgather(vals, ids, comms, m: int, k: int, n_dev: int,
                     select_min: bool):
    """All-gather the per-shard tables, select locally (the original
    merge; reference: knn_merge_parts.cuh)."""
    all_v = comms.allgather(vals)               # [n_dev, m, k]
    all_i = comms.allgather(ids)
    flat_v = jnp.transpose(all_v, (1, 0, 2)).reshape(m, n_dev * k)
    flat_i = jnp.transpose(all_i, (1, 0, 2)).reshape(m, n_dev * k)
    return _select_k(flat_v, k, select_min=select_min, input_indices=flat_i)


def _ring_merge_fallback(vals, ids, comms, axis, m: int, k: int,
                         n_dev: int, select_min: bool,
                         mc: Optional[int] = None):
    """The ppermute ring — the kernel's schedule, collective by
    collective: device ``i`` launches chunk ``(i−1) mod n_dev``'s
    partial, ships its running block right each hop, and merges the
    incoming partial with its local block for that chunk; after
    n_dev−1 hops device ``i`` owns chunk ``i`` fully merged. ``mc``
    overrides the chunk rows (the hier tier's outer-divisible pad)."""
    if mc is None:
        mc = _pk.ring_chunk_rows(m, n_dev)
    m_pad = mc * n_dev
    big = jnp.inf if select_min else -jnp.inf
    v = vals.astype(jnp.float32)
    # id width rides the policy (core.ids): an int64 billion-scale id
    # table must not truncate through the merge
    i = ids.astype(_ids.id_dtype_like(ids))
    if m_pad > m:
        v = jnp.pad(v, ((0, m_pad - m), (0, 0)), constant_values=big)
        i = jnp.pad(i, ((0, m_pad - m), (0, 0)), constant_values=-1)
    v = jnp.where(i < 0, big, v)  # uniform invalid sentinel (kernel parity)
    v3 = v.reshape(n_dev, mc, k)
    i3 = i.reshape(n_dev, mc, k)
    rank = comms.get_rank()
    c0 = (rank + n_dev - 1) % n_dev
    run_v = jax.lax.dynamic_index_in_dim(v3, c0, 0, keepdims=False)
    run_i = jax.lax.dynamic_index_in_dim(i3, c0, 0, keepdims=False)
    for s in range(n_dev - 1):
        run_v, run_i = comms.ring_topk_hop(run_v, run_i)
        c = (rank + 2 * n_dev - s - 2) % n_dev
        loc_v = jax.lax.dynamic_index_in_dim(v3, c, 0, keepdims=False)
        loc_i = jax.lax.dynamic_index_in_dim(i3, c, 0, keepdims=False)
        cat_v = jnp.concatenate([run_v, loc_v], axis=1)
        cat_i = jnp.concatenate([run_i, loc_i], axis=1)
        run_v, run_i = _select_k(cat_v, k, select_min=select_min,
                                 input_indices=cat_i)
    return run_v, run_i


def _merge_hier(vals, ids, outer: str, inner: str, m: int, k: int,
                select_min: bool, interpret: bool = False):
    """Two-level merge (ISSUE 19) — per-pod ring over ``inner`` (ICI),
    then ONE sparse survivor allgather over ``outer`` (DCN).

    Inner stage: the flat ring tier confined to this pod — the Pallas
    persistent kernel when the inner axis is eligible
    (:func:`~raft_tpu.ops.pallas_kernels.ring_topk_inner_ok`), the
    identical-schedule ppermute fallback otherwise — leaving each
    device its pod's fully-merged ``[mc, k]`` survivor block for its
    owned query chunk, ``mc`` padded so ``n_outer`` divides it.

    Outer stage: counterpart devices across pods hold the SAME query
    chunk, so each device takes ownership of ``mc_d = mc/n_outer`` of
    those rows and ONE all-to-all over the DCN axis ships pod ``e``'s
    sub-chunk ``f`` to outer-rank ``f`` — after the exchange this
    device holds every pod's k survivors (never raw candidates) for
    its owned rows, and selects k of ``n_outer·k`` locally. Counted
    ``op=alltoall, axis=<outer>``: ``mc·k`` entries per device =
    ``n_outer · mc_d · k``, the O(k·pods) k-survivor byte model the
    scaling CI asserts against the flat ring's stream.

    Each stage rides its own single-axis sub-communicator, so the
    per-axis ``comms.bytes{axis=ici|dcn}`` attribution falls out of the
    facade with no special casing."""
    inner_c = Comms(inner)
    outer_c = Comms(outer)
    n_inner = int(_axis_size(inner))
    n_outer = int(_axis_size(outer))
    mc = hier_chunk_rows(m, n_inner, n_outer)
    kernel_ok = (_pk._on_tpu()
                 and _pk.ring_topk_inner_ok(m, k, n_inner)
                 and mc == _pk.ring_chunk_rows(m, n_inner)
                 and jnp.dtype(ids.dtype).itemsize < 8)
    if kernel_ok:
        inner_c.count_ring_topk(
            n_inner - 1,
            jax.ShapeDtypeStruct((mc, k), jnp.float32),
            jax.ShapeDtypeStruct((mc, k), jnp.int32))
        pv, pi = _pk.ring_topk_merge(vals, ids, k, inner, n_inner,
                                     select_min, interpret=interpret,
                                     outer_axis=outer)
    else:
        pv, pi = _ring_merge_fallback(vals, ids, inner_c, inner, m, k,
                                      n_inner, select_min, mc=mc)
    mc_d = mc // n_outer
    # survivor exchange: one all-to-all over DCN — pod e's sub-chunk f
    # moves to outer-rank f, so this device receives every pod's
    # survivors for ITS sub-chunk d (row-block e of the result = pod
    # e's rows [d·mc_d, (d+1)·mc_d) of the pod-merged chunk)
    ex_v = outer_c.alltoall(pv).reshape(n_outer, mc_d, k)
    ex_i = outer_c.alltoall(pi).reshape(n_outer, mc_d, k)
    flat_v = jnp.transpose(ex_v, (1, 0, 2)).reshape(mc_d, n_outer * k)
    flat_i = jnp.transpose(ex_i, (1, 0, 2)).reshape(mc_d, n_outer * k)
    return _select_k(flat_v, k, select_min=select_min, input_indices=flat_i)


def merge_topk(vals: jax.Array, ids: jax.Array,
               axis: Union[str, Sequence[str]], m: int, k: int,
               n_dev: int, select_min: bool, tier: str = "allgather",
               impl: Optional[str] = None, interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """Cross-shard candidate merge — runs INSIDE ``shard_map`` over
    ``axis``. ``vals``/``ids`` [m, k] are this shard's local top-k
    (global ids, -1 invalid, invalid keys at the select sentinel).

    The allgather tier returns the replicated [m, k] result; the ring
    tier returns this device's owned query chunk; the hier tier (2-D
    ``axis=(outer, inner)``) its owned (inner-chunk, outer-sub-chunk)
    block (pair with :func:`merge_out_spec` / :func:`merged_rows`).
    All traffic rides the ``Comms`` facade — allgather merges count the
    materialized table, ring merges count n_dev−1 surviving-block hops
    under ``op=ring_topk``, hier merges count the per-pod ring on the
    inner axis plus one survivor allgather on the outer — so the tiers'
    merge-phase bytes are directly comparable in ``comms.bytes`` (the
    dryrun's scaling assertions)."""
    expects(tier in MERGE_TIERS, "unknown merge tier %r", tier)
    expects(vals.shape == (m, k) and ids.shape == (m, k),
            "merge_topk expects [m, k] local tables (got %s/%s for "
            "m=%d k=%d)", vals.shape, ids.shape, m, k)
    if tier == "hier":
        expects(not isinstance(axis, str) and len(tuple(axis)) == 2,
                "hier merge needs axis=(outer, inner), got %r", axis)
        outer, inner = axis
        return _merge_hier(vals, ids, outer, inner, m, k, select_min,
                           interpret=interpret)
    comms = Comms(axis)
    if tier == "allgather":
        return _merge_allgather(vals, ids, comms, m, k, n_dev, select_min)
    if impl == "ring_kernel" and jnp.dtype(ids.dtype).itemsize >= 8:
        # the Pallas kernel is int32-only by construction; an int64
        # billion-scale id table rides the identical-schedule ppermute
        # fallback instead of silently truncating through the kernel
        _obs_spans.count_fallback("parallel.merge", "id_width")
        impl = "ring_ppermute"
    if impl == "ring_kernel":
        mc = _pk.ring_chunk_rows(m, n_dev)
        # the kernel's remote DMAs bypass lax: attribute its hop traffic
        # through the facade at trace time (GL10's telemetry invariant).
        # Counted at the LOGICAL [mc, k] block — the facade-wide
        # convention (every verb counts shape × itemsize): physically
        # the kernel ships lane-padded [mc, 128] buffers, exactly as
        # XLA's tiled layout pads the allgather tier's [m, k] tables,
        # so the tier-vs-tier comparison stays like-for-like
        comms.count_ring_topk(
            n_dev - 1,
            jax.ShapeDtypeStruct((mc, k), jnp.float32),
            jax.ShapeDtypeStruct((mc, k), jnp.int32))
        return _pk.ring_topk_merge(vals, ids, k, axis, n_dev, select_min,
                                   interpret=interpret)
    return _ring_merge_fallback(vals, ids, comms, axis, m, k, n_dev,
                                select_min)
