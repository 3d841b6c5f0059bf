"""Balanced (hierarchical) k-means — the trainer behind every IVF index.

TPU-native counterpart of ``raft::cluster::kmeans_balanced``
(cluster/kmeans_balanced.cuh:76 fit, detail/kmeans_balanced.cuh — 1097 LoC:
mesocluster hierarchy :758, adjust_centers balancing). Same two-level
design, TPU-shaped execution:

1. fit ~√k *mesoclusters* with plain Lloyd;
2. partition each mesocluster's rows into fine clusters (count ∝ meso
   size), fitting per-meso Lloyd on padded, weight-masked row blocks
   (static shapes per meso — the TPU version of the reference's
   variable-size mesocluster kernels);
3. finish with joint Lloyd sweeps over all fine centers, re-seeding
   under-populated clusters from the fattest clusters' far points each
   sweep (the reference's ``adjust_centers`` balancing pass).

Balance matters doubly on TPU: IVF lists are padded blocks, so variance in
list size is wasted HBM *and* wasted scan FLOPs.

Supports metric="l2" and "cosine" (rows are L2-normalized first, as the
reference does for spherical kmeans).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core.errors import expects
from raft_tpu.core.tracing import traced
from raft_tpu.distance.fused_l2_nn import fused_l2_nn_argmin
from raft_tpu.cluster.kmeans import _update_centroids, init_random
from raft_tpu.random.rng import RngState
from raft_tpu.utils.precision import get_precision


@dataclasses.dataclass
class KMeansBalancedParams:
    """reference: ``kmeans_balanced_params`` (cluster/kmeans_balanced_types.hpp)."""

    n_iters: int = 20
    metric: str = "l2"  # "l2" | "cosine"
    seed: int = 0
    mesocluster_factor: float = 1.0  # n_meso = factor * sqrt(k)


def _maybe_normalize(x: jax.Array, metric: str) -> jax.Array:
    if metric == "cosine":
        n = jnp.sqrt(jnp.maximum(jnp.sum(x * x, axis=-1, keepdims=True), 1e-12))
        return x / n
    return x


@partial(jax.jit, static_argnames=("n_clusters", "n_iters"))
def _balanced_lloyd(x, w, c0, n_clusters: int, n_iters: int, key,
                    split_iters=0):
    """Lloyd sweeps with per-sweep re-seeding of starved clusters
    (reference: adjust_centers, detail/kmeans_balanced.cuh).

    ``split_iters`` (traced) picks the re-seed target per sweep: sweeps
    ``i < split_iters`` re-seed at random far-ish rows *inside the
    fattest clusters* (best cluster BALANCE — measured on clustered
    100K×1024 data it cuts the max list from ~45× the mean to ~2×,
    which is exactly what the padded-list IVF layout needs; the
    deterministic farthest rows would be the boundary ring, which
    leaves the dense core as one cluster); later sweeps re-seed at the
    globally farthest points (best cluster QUALITY — the
    kmeans++-flavored choice for codebook/meso fits). Traced rather
    than static so both phases share ONE compiled program — one XLA
    compilation instead of one per variant."""
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    total_w = jnp.maximum(jnp.sum(wf), 1e-12)
    # a cluster is "starved" below this fraction of the average mass
    starve_thresh = 0.25 * total_w / n_clusters

    def body(i, centroids):
        d2, labels = fused_l2_nn_argmin(xf, centroids)
        new_c, counts = _update_centroids(xf, wf, labels, n_clusters, centroids)
        starved = counts < starve_thresh
        u = jax.random.uniform(jax.random.fold_in(key, i),
                               (xf.shape[0],), minval=1e-6)
        split_score = counts[labels] + u * d2 / (jnp.max(d2) + 1e-12)
        far_score = jnp.where(
            wf > 0, jnp.where(i < split_iters, split_score, d2), -jnp.inf)
        # re-seed candidates need no exact order — the hardware approx
        # top-k replaces a full [n] sort per sweep (measured ~20 s at
        # n=2M, k=8192: it dominated billion-scale coarse training)
        _, far_idx = lax.approx_max_k(far_score, n_clusters,
                                      recall_target=0.9)
        # rank starved clusters; the j-th starved cluster takes the j-th
        # farthest point as its new center
        starved_rank = jnp.cumsum(starved.astype(jnp.int32)) - 1
        take_idx = far_idx[jnp.clip(starved_rank, 0, n_clusters - 1)]
        reseeded = xf[take_idx]
        new_c = jnp.where(starved[:, None], reseeded, new_c)
        return new_c

    return lax.fori_loop(0, n_iters, body, c0.astype(jnp.float32))


@jax.jit
def _balanced_sweep_batched(xs, ws, cs, kmask):
    """One Lloyd sweep of every mesocluster's fine fit: padded row
    blocks ``xs [M, T, d]``, weight masks ``ws [M, T]`` (0 = pad),
    centers ``cs [M, k, d]``, active-center masks ``kmask [M, k]`` (a
    meso wanting fewer than ``k`` centers masks the rest — inactive
    slots are pinned far away so they never attract rows nor re-seed).
    One batched einsum assigns, a one-hot MXU contraction updates, and
    starved clusters re-seed per meso — the batched twin of one
    :func:`_balanced_lloyd` sweep."""
    xf = xs.astype(jnp.float32)
    wf = ws.astype(jnp.float32)
    km = kmask.astype(jnp.bool_)
    k = cs.shape[1]
    k_active = jnp.maximum(jnp.sum(km, axis=1).astype(jnp.float32), 1.0)
    total_w = jnp.maximum(jnp.sum(wf, axis=1), 1e-12)        # [M]
    starve_thresh = 0.25 * total_w / k_active                # [M]
    x_sq = jnp.sum(xf * xf, axis=-1)                         # [M, T]
    FAR = jnp.float32(1e15)

    cs = jnp.where(km[..., None], cs.astype(jnp.float32), FAR)  # park
    c_sq = jnp.sum(cs * cs, axis=-1)                         # [M, k]
    g = jnp.einsum("mtd,mkd->mtk", xf, cs, precision=get_precision(),
                   preferred_element_type=jnp.float32)
    d2 = jnp.maximum(x_sq[..., None] + c_sq[:, None, :] - 2.0 * g, 0.0)
    labels = jnp.argmin(d2, axis=-1)                         # [M, T]
    dmin = jnp.min(d2, axis=-1)
    oh = jax.nn.one_hot(labels, k, dtype=jnp.float32) * wf[..., None]
    counts = jnp.sum(oh, axis=1)                             # [M, k]
    sums = jnp.einsum("mtk,mtd->mkd", oh, xf, precision=get_precision(),
                      preferred_element_type=jnp.float32)
    new_c = jnp.where(counts[..., None] > 0,
                      sums / jnp.maximum(counts[..., None], 1e-12), cs)
    starved = (counts < starve_thresh[:, None]) & km
    far_score = jnp.where(wf > 0, dmin, -jnp.inf)
    _, far_idx = lax.top_k(far_score, k)                     # [M, k]
    starved_rank = jnp.cumsum(starved.astype(jnp.int32), axis=1) - 1
    take = jnp.take_along_axis(far_idx,
                               jnp.clip(starved_rank, 0, k - 1), axis=1)
    reseeded = jnp.take_along_axis(
        xf, take[..., None].astype(jnp.int32), axis=1)       # [M, k, d]
    return jnp.where(starved[..., None], reseeded, new_c)


def _balanced_lloyd_batched(xs, ws, c0s, kmask, n_iters: int):
    """All mesoclusters' fine Lloyd fits: ``n_iters`` sweeps of
    :func:`_balanced_sweep_batched`, one compiled program for every
    meso (a per-meso loop would compile one program per distinct
    (size, k)).

    The sweeps are dispatched one program each, not chained in one
    ``fori_loop``: on a TPU v5e (JAX 0.9.0, libtpu 0.0.34) a program
    holding two or more of these sweeps, looped or unrolled, computes
    other centers than the same sweeps run one program at a time. On
    200,000 hard-synthetic rows into 256 lists the chained fit's
    largest list was 7,073 rows (mean 781), still 3,192 after the final
    sweeps — more than the IVF spill could place. One sweep per program
    matches the CPU sweep for sweep (labels, counts and re-seeds
    equal). Dispatch is asynchronous, so the host never waits between
    sweeps."""
    cs = c0s.astype(jnp.float32)
    for _ in range(n_iters):
        cs = _balanced_sweep_batched(xs, ws, cs, kmask)
    return cs


def build_clusters(
    x: jax.Array,
    n_clusters: int,
    params: Optional[KMeansBalancedParams] = None,
    sample_weights: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-level balanced clustering → (centers, labels, sizes).

    Counterpart of ``kmeans_balanced::helpers::build_clusters``
    (cluster/kmeans_balanced.cuh) — used directly for PQ codebook training.
    """
    if params is None:
        params = KMeansBalancedParams()
    xn = _maybe_normalize(jnp.asarray(x, jnp.float32), params.metric)
    n = xn.shape[0]
    w = jnp.ones((n,), jnp.float32) if sample_weights is None else sample_weights
    key = RngState(params.seed).key()
    c0 = init_random(key, xn, n_clusters)
    centers = _balanced_lloyd(xn, w, c0, n_clusters, params.n_iters, key)
    centers = _maybe_normalize(centers, params.metric)
    _, labels = fused_l2_nn_argmin(xn, centers)
    sizes = jax.ops.segment_sum(jnp.ones_like(w), labels, num_segments=n_clusters)
    return centers, labels, sizes.astype(jnp.int32)


# Above this fraction of the trainset, level-2 sampling truncation is a
# visible clustering-bias source, not a rounding error — warn.
_LEVEL2_DROP_WARN_FRAC = 0.02


def _warn_level2_drop(n_drop: int, n: int, cap: int) -> None:
    """Surface level-2 sampling bias (ADVICE r5): a skew-hot mesocluster
    past the 2×-mean block cap trains its fine centers on a TRUNCATED
    sample. Tolerable when rare (the trainset is a subsample anyway);
    silently losing a meaningful fraction of the trainset is not."""
    frac = n_drop / max(n, 1)
    if frac > _LEVEL2_DROP_WARN_FRAC:
        from raft_tpu.core import logging as _log
        _log.warn("kmeans_balanced: level-2 sampling dropped %d/%d "
                  "training rows (%.1f%%) past the per-mesocluster cap "
                  "%d — fine clusters of hot mesoclusters train on "
                  "truncated samples", n_drop, n, 100.0 * frac, cap)


@traced("raft_tpu.kmeans_balanced.fit")
# the hierarchical fit partitions fine-cluster quotas on the host BY
# DESIGN (documented in the level-2 block below) — its syncs are the
# algorithm, not an accident
def fit(  # graftlint: disable-fn=GL01
    x: jax.Array,
    n_clusters: int,
    params: Optional[KMeansBalancedParams] = None,
) -> jax.Array:
    """Hierarchical balanced fit → centers [n_clusters, d]
    (reference: kmeans_balanced::fit, cluster/kmeans_balanced.cuh:76)."""
    if params is None:
        params = KMeansBalancedParams()
    x = jnp.asarray(x, jnp.float32)
    xn = _maybe_normalize(x, params.metric)
    n, d = xn.shape
    expects(n_clusters <= n, "n_clusters=%d > n_samples=%d", n_clusters, n)
    key = RngState(params.seed).key()

    n_meso = max(1, min(n_clusters,
                        int(params.mesocluster_factor * math.isqrt(n_clusters))))
    if n_meso <= 1 or n_clusters <= 8:
        c0 = init_random(key, xn, n_clusters)
        w = jnp.ones((n,), jnp.float32)
        centers = _balanced_lloyd(xn, w, c0, n_clusters, params.n_iters, key)
        return _maybe_normalize(centers, params.metric)

    # level 1: mesoclusters (reference: detail/kmeans_balanced.cuh:758)
    w = jnp.ones((n,), jnp.float32)
    meso_c0 = init_random(key, xn, n_meso)
    meso_centers = _balanced_lloyd(xn, w, meso_c0, n_meso, params.n_iters, key)
    _, meso_labels = fused_l2_nn_argmin(xn, meso_centers)
    meso_labels_h = np.asarray(meso_labels)
    sizes = np.bincount(meso_labels_h, minlength=n_meso)

    # fine cluster counts ∝ mesocluster size, summing exactly to n_clusters
    quota = sizes / max(sizes.sum(), 1) * n_clusters
    fine_k = np.maximum(1, np.floor(quota).astype(np.int64))
    # distribute the remainder by largest fractional part
    while fine_k.sum() > n_clusters:
        fine_k[np.argmax(fine_k)] -= 1
    rem = n_clusters - fine_k.sum()
    if rem > 0:
        order = np.argsort(-(quota - np.floor(quota)))
        for j in order[:rem]:
            fine_k[j] += 1

    # level 2: per-mesocluster fine clustering on padded, masked row
    # blocks, batched into ONE compiled program. The rows are
    # partitioned into per-meso blocks ON DEVICE (ivf_common.pack_lists
    # — the same sort+scatter the IVF packers use): the previous host
    # partition shipped the trainset to the host and the padded blocks
    # back, ~0.75 GB of host↔device traffic at 500K×128, plus one
    # compile per pow2 size bucket. Block capacity is
    # capped at 2× the mean meso size; overflow rows of a skewed meso
    # are dropped from ITS TRAINING SAMPLE only (the trainset is a
    # subsample anyway — balance matters, completeness doesn't).
    from raft_tpu.neighbors import ivf_common as _ic

    avg_meso = max(1, -(-n // n_meso))
    L_meso = max(8, -(-2 * avg_meso // 8) * 8)
    (subs,), _mids, _sd, _drop, _addr = _ic.pack_lists_jit(
        [xn], meso_labels, jnp.arange(n, dtype=jnp.int32),
        n_lists=n_meso, L=L_meso, fill_values=[jnp.zeros((), xn.dtype)])
    _warn_level2_drop(int(_drop), n, L_meso)
    masks = (_mids >= 0).astype(jnp.float32)            # [n_meso, L]
    # active center count per meso, capped by its AVAILABLE block rows
    # (a meso past the block cap has only L_meso rows to fit on; the
    # global shortfall is backfilled below like empty mesos)
    sizes_c = np.minimum(np.maximum(sizes, 1), L_meso)
    k_active = np.maximum(np.minimum(np.minimum(fine_k, sizes), L_meso), 1)
    k_pad = int(k_active.max())
    # init: strided member rows of each block, spread over the FULL
    # member range per meso with linspace-style endpoints (first AND
    # last row included — a global k_pad stride clustered a small-
    # fine_k meso's inits in its first rows, measured to cost balance)
    pos = np.minimum(np.arange(k_pad)[None, :] * (sizes_c[:, None] - 1)
                     // np.maximum(k_active[:, None] - 1, 1),
                     sizes_c[:, None] - 1).astype(np.int32)
    c0s = jnp.take_along_axis(subs, jnp.asarray(pos)[..., None], axis=1)
    kmask_h = (np.arange(k_pad)[None, :]
               < k_active[:, None]).astype(np.float32)
    cms = np.asarray(_balanced_lloyd_batched(
        subs, masks, c0s, jnp.asarray(kmask_h), params.n_iters))
    fine_centers = [cms[m, :int(k_active[m])]
                    for m in range(n_meso) if sizes[m] > 0]
    centers = jnp.asarray(np.concatenate(fine_centers, axis=0))
    if centers.shape[0] < n_clusters:  # lost slots to empty mesoclusters
        extra = init_random(jax.random.fold_in(key, 999), xn,
                            n_clusters - centers.shape[0])
        centers = jnp.concatenate([centers, extra], axis=0)

    # final joint sweeps over the full data: fat-splitting sweeps drive
    # list sizes toward the mean, then two plain quality sweeps settle
    # the centers — one compiled program (split_iters is traced)
    sweeps = max(2, params.n_iters // 4)
    centers = _balanced_lloyd(xn, w, centers, n_clusters, sweeps + 2, key,
                              split_iters=sweeps)
    return _maybe_normalize(centers, params.metric)


@traced("raft_tpu.kmeans_balanced.predict")
def predict(centers: jax.Array, x: jax.Array,
            params: Optional[KMeansBalancedParams] = None) -> jax.Array:
    """Nearest balanced-center labels (reference: kmeans_balanced::predict)."""
    metric = params.metric if params is not None else "l2"
    xn = _maybe_normalize(jnp.asarray(x, jnp.float32), metric)
    _, labels = fused_l2_nn_argmin(xn, centers)
    return labels


@partial(jax.jit, static_argnames=("row_tile", "k"))
def _topk_labels(centers, xn, row_tile: int, k: int):
    c_sq = jnp.sum(centers * centers, axis=1)
    m, d = xn.shape
    n_tiles = -(-m // row_tile)
    xp = jnp.pad(xn, ((0, n_tiles * row_tile - m), (0, 0)))

    def tile(xt):
        g = lax.dot_general(xt, centers, (((1,), (1,)), ((), ())),
                            precision=get_precision(),
                            preferred_element_type=jnp.float32)
        d2 = c_sq[None, :] - 2.0 * g  # rank-equivalent (x² constant/row)
        _, topk = lax.top_k(-d2, k)
        return topk.astype(jnp.int32)

    out = lax.map(tile, xp.reshape(n_tiles, row_tile, d))
    return out.reshape(n_tiles * row_tile, k)[:m]


def predict_topk(centers: jax.Array, x: jax.Array, k: int = 2,
                 params: Optional[KMeansBalancedParams] = None) -> jax.Array:
    """``k`` nearest centers per row → [m, k] int32 — feeds the packers'
    spill-cascade capacity capping (ivf_common.spill_assignments).
    Row-tiled so the [tile, n_lists] distance block stays bounded."""
    metric = params.metric if params is not None else "l2"
    xn = _maybe_normalize(jnp.asarray(x, jnp.float32), metric)
    k = min(k, centers.shape[0])
    tile = max(1024, min(x.shape[0], (256 << 20) // max(4 * centers.shape[0], 1)))
    return _topk_labels(centers, xn, -(-tile // 8) * 8, k)


def predict2(centers: jax.Array, x: jax.Array,
             params: Optional[KMeansBalancedParams] = None) -> jax.Array:
    """Two nearest centers per row → [m, 2] int32 (see predict_topk)."""
    return predict_topk(centers, x, 2, params)
