"""Distributed ANN search patterns — sharded & replicated index search.

The multi-GPU patterns the reference enables downstream (SURVEY.md §2.15):
*sharded-index* search = per-shard top-k + cross-shard merge via
``knn_merge_parts``, and *replicated-index* search = data-parallel query
fan-out. Here both are single SPMD programs: ``shard_map`` over a mesh axis
with ``lax`` collectives doing the merge on ICI — no NCCL, no Dask.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from raft_tpu.core.errors import expects
from raft_tpu.core import ids as _ids
from raft_tpu.distance import DistanceType, SELECT_MIN, resolve_metric
from raft_tpu.neighbors import brute_force
from raft_tpu.parallel import merge as _merge
from raft_tpu.parallel.comms import Comms


def _pad_rows(x: jax.Array, multiple: int) -> Tuple[jax.Array, int]:
    n = x.shape[0]
    padded = -(-n // multiple) * multiple
    if padded == n:
        return x, n
    return jnp.pad(x, ((0, padded - n), (0, 0))), n


def sharded_knn(
    dataset: jax.Array,
    queries: jax.Array,
    k: int,
    mesh: Mesh,
    axis: Union[str, Sequence[str]] = "shard",
    metric="sqeuclidean",
    merge: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN over an index sharded across a mesh axis.

    Each device scans its local shard (tiled brute force on the MXU),
    takes a local top-k, and the per-shard candidates merge through the
    shared cross-shard merge tier (``parallel.merge``) — allgather-and-
    select or the ring reduce-scatter-of-top-k exchange, picked by
    ``merge`` ("auto" defers to ``RAFT_TPU_RING_TOPK``) — the
    reference's sharded-index pattern (per-shard select +
    ``knn_merge_parts``, knn_brute_force.cuh:276) as one SPMD program.

    ``axis`` may be a 2-tuple ``(outer, inner)`` over a 2-D hier mesh
    (:func:`raft_tpu.parallel.mesh.hier_mesh`): the index shards over
    both axes jointly (outer-major) and, when the outer axis is
    DCN-labeled, the merge auto-escalates to the two-level ``hier``
    tier (per-pod ring over ICI, one sparse survivor exchange over
    DCN).

    Returns (distances [m, k], global indices [m, k]) — replicated
    under the allgather tier, query-sharded under the ring/hier tiers.
    """
    mt = resolve_metric(metric)
    select_min = SELECT_MIN[mt]
    n_dev, whole_mesh, hier_axes = _merge.resolve_exchange(mesh, axis)
    n = dataset.shape[0]
    m = queries.shape[0]
    padded, _ = _pad_rows(dataset, n_dev)
    shard_size = padded.shape[0] // n_dev
    expects(k <= shard_size, "k=%d exceeds shard size %d", k, shard_size)
    pad_val = jnp.inf if select_min else -jnp.inf
    comms = Comms(axis)  # counted collectives (comms.ops/comms.bytes)
    tier, impl = _merge.merge_tier(
        n_dev, m, k, explicit=merge,
        whole_mesh=whole_mesh, hier_axes=hier_axes)

    def local_search(ds_shard, q):
        rank = comms.get_rank()
        idx = brute_force.build(ds_shard, metric=mt)
        vals, ids = brute_force.knn(idx, q, k)
        # global-id remap in the policy dtype of the PADDED total row
        # count — rank·shard_size overflows int32 past 2³¹ pod rows
        # even though every per-shard id fits it, and pad-row gids
        # reach n_dev·shard_size − 1 > n, so the width must cover the
        # padding or the `gids < n` mask below sees wrapped negatives
        gids = _ids.global_ids(rank, shard_size, ids,
                               n_total=n_dev * shard_size)
        vals = jnp.where(gids < n, vals, pad_val)  # mask padded rows
        gids = jnp.where(gids < n, gids, -1)
        return _merge.merge_topk(vals, gids, axis, m, k, n_dev,
                                 select_min, tier=tier, impl=impl)

    out_spec = _merge.merge_out_spec(tier, axis)
    fn = shard_map(
        local_search, mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=(out_spec, out_spec),
        check_vma=False,
    )
    rv, ri = fn(padded, queries)
    return rv[:m], ri[:m]


def replicated_knn(
    dataset: jax.Array,
    queries: jax.Array,
    k: int,
    mesh: Mesh,
    axis: str = "shard",
    metric="sqeuclidean",
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN with a replicated index and queries sharded over the mesh —
    the reference's replicated-index throughput pattern (each worker holds
    the full index, queries split). Returns sharded (dists, indices)."""
    mt = resolve_metric(metric)
    n_dev = mesh.shape[axis]
    q_padded, m = _pad_rows(queries, n_dev)

    def local_search(q_shard, ds):
        idx = brute_force.build(ds, metric=mt)
        return brute_force.knn(idx, q_shard, k)

    fn = shard_map(
        local_search, mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=(P(axis, None), P(axis, None)),
        check_vma=False,
    )
    vals, ids = fn(q_padded, dataset)
    return vals[:m], ids[:m]
