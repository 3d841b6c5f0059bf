"""Distributed k-means — sample-sharded SPMD Lloyd.

The reference's MNMG kmeans pattern (SURVEY.md §3.5: each worker runs the
local fused-L2 assign + local centroid sums, then ``allreduce`` merges the
sums — cuML on raft-dask/NCCL). Here the whole loop is one SPMD program:
``shard_map`` over the sample axis, ``lax.psum`` over ICI for the merge.

**Role in the distributed index build** (``parallel.build``): the
chunked pod builders train their coarse quantizer in one of two modes —
``coarse="replicated"`` (default) runs the single-host balanced-kmeans
trainer over the allgatherv'd cross-shard trainset, which keeps the
built index bit-identical to the single-host ``build_chunked``;
``coarse="distributed"`` routes HERE (:func:`fit`'s psum Lloyd over the
*sharded* trainset) when even the trainset is too big to replicate —
centers then differ from the single-host build (a different, equally
valid optimum), trading the sha-parity guarantee for trainset scale.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from raft_tpu.distance.fused_l2_nn import fused_l2_nn_argmin
from raft_tpu.cluster.kmeans import KMeansParams, init_random
from raft_tpu.core.tracing import traced
from raft_tpu.random.rng import RngState


@traced("raft_tpu.distributed_kmeans.fit")
def fit(
    params: KMeansParams,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "shard",
    init_centroids: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed Lloyd fit over a sample-sharded dataset.

    ``x`` is [n, d], sharded (or shardable) over ``axis``; rows are padded
    to the device count with zero weights. Returns replicated
    (centroids, inertia, n_iter).

    ``weights`` (optional, [n] f32) weight the samples — the MNMG
    sample-weight support the reference's cuML kmeans carries. The
    distributed build's ``coarse="distributed"`` mode uses zero weights
    to mask the pad rows of its stacked ragged per-shard sample, so the
    sample never has to be gathered/replicated: each shard's slice stays
    its own and only the [k, d] centroid sums ride the psum. Zero-weight
    rows contribute to no centroid and no inertia; random init draws
    from positive-weight rows only.
    """
    # deferred: parallel.ivf imports this module, so a top-level comms
    # import would be circular
    from raft_tpu.parallel.comms import Comms

    comms = Comms(axis)
    n, d = x.shape
    k = params.n_clusters
    n_dev = mesh.shape[axis]
    padded_n = -(-n // n_dev) * n_dev
    w = (jnp.ones((n,), jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))
    if padded_n != n:
        x = jnp.pad(x, ((0, padded_n - n), (0, 0)))
        w = jnp.pad(w, (0, padded_n - n))

    if init_centroids is None:
        key = RngState(params.seed).key()
        if weights is None:
            init_centroids = init_random(key, x[:n], k)
        else:
            # draw initial centroids from REAL rows only — a zero-weight
            # pad row picked as an init would seed a dead centroid at
            # the origin (weights are concrete here: this runs on the
            # host before the SPMD program)
            real = jnp.flatnonzero(w[:n] > 0)
            init_centroids = init_random(key, x[real], k)

    def step(x_shard, w_shard, centroids):
        """One Lloyd iteration: local assign + psum-merged update."""
        d2, labels = fused_l2_nn_argmin(x_shard, centroids)
        local_sums = jax.ops.segment_sum(x_shard * w_shard[:, None], labels,
                                         num_segments=k)
        local_counts = jax.ops.segment_sum(w_shard, labels, num_segments=k)
        local_inertia = jnp.sum(w_shard * d2)
        # the reference's allreduce (core/comms.hpp:344), via the Comms
        # facade so the merge traffic is counted per op × axis
        sums = comms.allreduce(local_sums)
        counts = comms.allreduce(local_counts)
        inertia = comms.allreduce(local_inertia)
        new_c = jnp.where(counts[:, None] > 0,
                          sums / jnp.maximum(counts[:, None], 1e-12), centroids)
        return new_c, inertia

    def fit_loop(x_shard, w_shard, c0):
        def cond(carry):
            _, shift2, it, _ = carry
            return (it < params.max_iter) & (shift2 > params.tol * params.tol)

        def body(carry):
            c, _, it, _ = carry
            new_c, inertia = step(x_shard, w_shard, c)
            return new_c, jnp.sum((new_c - c) ** 2), it + 1, inertia

        init = (c0, jnp.array(jnp.inf, jnp.float32), jnp.array(0, jnp.int32),
                jnp.array(jnp.inf, jnp.float32))
        c, _, n_iter, inertia = lax.while_loop(cond, body, init)
        return c, inertia, n_iter

    fn = shard_map(
        fit_loop, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return fn(x.astype(jnp.float32), w, init_centroids.astype(jnp.float32))


@traced("raft_tpu.distributed_kmeans.predict")
def predict(centroids: jax.Array, x: jax.Array, mesh: Mesh,
            axis: str = "shard") -> jax.Array:
    """Sharded nearest-centroid assignment; labels return sharded."""
    n = x.shape[0]
    n_dev = mesh.shape[axis]
    padded_n = -(-n // n_dev) * n_dev
    if padded_n != n:
        x = jnp.pad(x, ((0, padded_n - n), (0, 0)))

    fn = shard_map(
        lambda xs, c: fused_l2_nn_argmin(xs, c)[1], mesh=mesh,
        in_specs=(P(axis, None), P()), out_specs=P(axis),
        check_vma=False,
    )
    return fn(x.astype(jnp.float32), centroids)[:n]
