"""Balanced (hierarchical) k-means — the trainer behind every IVF index.

TPU-native counterpart of ``raft::cluster::kmeans_balanced``
(cluster/kmeans_balanced.cuh:76 fit, detail/kmeans_balanced.cuh — 1097 LoC:
mesocluster hierarchy :758, adjust_centers balancing). Same two-level
design, TPU-shaped execution:

1. fit ~√k *mesoclusters*;
2. partition each mesocluster's rows into fine clusters (count ∝ meso
   size), fitting per-meso Lloyd on padded, weight-masked row blocks
   (static shapes per meso — the TPU version of the reference's
   variable-size mesocluster kernels);
3. finish with joint Lloyd sweeps over all fine centers.

Every level's sweeps are balanced (the reference's ``adjust_centers``
pass): after each update but the last, the smallest clusters are
re-seeded by splitting the fattest (:func:`_split_fattest`), as many as
are starved (under a quarter of the mean) or fat (over 1.75 times
it).

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


#: a cluster holding under this share of the mean mass is starved
#: (reference: adjust_centers' balancing threshold)
_STARVE_SHARE = 0.25
#: a cluster holding over this multiple of the mean mass is fat
_FAT_SHARE = 1.75
#: how far apart a split sets the two halves' centers, as a share of the
#: distance from the split cluster's center to the row that directs it
_SPLIT_SCALE = 1e-3


def _member_rows(labels, weights, n_clusters, key):
    """A row of each non-empty cluster, drawn at random from ``key``:
    the weighted row whose random draw is the cluster's largest, a
    masked max over ``[rows, clusters]`` (no scatter)."""
    u = jax.random.uniform(key, labels.shape, minval=0.5, maxval=1.0)
    u = jnp.where(weights > 0, u, 0.0)
    hit = labels[:, None] == jnp.arange(n_clusters)[None, :]
    return jnp.argmax(jnp.where(hit, u[:, None], -1.0), axis=0)


def _split_fattest(centers, counts, active, step, mean):
    """Re-seed the smallest clusters by splitting the fattest (the
    reference's adjust_centers moves a starved center toward a row of a
    large cluster; this is FAISS's ``split_clusters`` form of it). As
    many are re-seeded as there are starved clusters (under
    ``_STARVE_SHARE`` of ``mean``, the mean mass) or fat ones (over
    ``_FAT_SHARE`` of it), whichever is more, and at most half of them:
    the r-th smallest cluster takes the r-th largest one's center, and
    the two are set apart by twice the donor's ``step`` (per cluster: a
    short step toward one of its rows), so the next assignment divides
    the fat cluster's rows by the hyperplane through its center across
    that row's direction, and the small one's rows go to their
    next-nearest centers. A donor must hold at least ``mean``. Only
    ``active`` clusters take part.

    Re-seeding at a far or random single row instead leaves a center
    that only its own row's neighbourhood reaches: on data of many
    small clusters it stays starved, sweep after sweep. Every choice
    here is a sort of the ``[k]`` counts, so both backends take the
    same one. Returns ``(centers, re-seeded)``.
    """
    k, d = centers.shape
    n_starved = jnp.sum(active & (counts < _STARVE_SHARE * mean))
    n_fat = jnp.sum(active & (counts > _FAT_SHARE * mean))
    ascending = jnp.argsort(jnp.where(active, counts, jnp.inf))
    descending = jnp.argsort(jnp.where(active, -counts, jnp.inf))
    rank = jnp.zeros((k,), jnp.int32).at[ascending].set(
        jnp.arange(k, dtype=jnp.int32))              # 0 = smallest
    donor = descending[rank]
    n_take = jnp.minimum(jnp.maximum(n_starved, n_fat),
                         jnp.sum(active) // 2)
    take = (active & (rank < n_take)
            & (counts[donor] >= mean) & (counts < counts[donor]))
    step = jnp.where(take[:, None], step[donor], 0.0)
    out = jnp.where(take[:, None], centers[donor] + step, centers)
    out = out.at[donor].add(-step)                   # the donor's half
    return out, jnp.sum(take.astype(jnp.int32))


@partial(jax.jit, static_argnames=("n_clusters", "n_iters"))
def _balanced_lloyd(x, w, c0, n_clusters: int, n_iters: int, key):
    """Lloyd sweeps that keep the clusters balanced: after each sweep's
    update but the last, the smallest clusters are re-seeded by
    splitting the fattest, as many as are starved or fat
    (:func:`_split_fattest`). The last sweep only updates, so
    each returned center is the mean of the rows it holds. Returns
    ``(centers, clusters re-seeded over all sweeps)``."""
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    total_w = jnp.maximum(jnp.sum(wf), 1e-12)

    def body(i, carry):
        centroids, n_reseeded = carry
        _, labels = fused_l2_nn_argmin(xf, centroids)
        new_c, counts = _update_centroids(xf, wf, labels, n_clusters,
                                          centroids)
        member = _member_rows(labels, wf, n_clusters,
                              jax.random.fold_in(key, i))
        step = _SPLIT_SCALE * (xf[member] - new_c)
        active = jnp.broadcast_to(i < n_iters - 1, counts.shape)
        new_c, n = _split_fattest(new_c, counts, active, step,
                                  total_w / n_clusters)
        return new_c, n_reseeded + n

    return lax.fori_loop(0, n_iters, body,
                         (c0.astype(jnp.float32), jnp.int32(0)))


@jax.jit
def _balanced_sweep_batched(xs, ws, cs, kmask, key, reseed):
    """One Lloyd sweep of every mesocluster's fine fit: padded row
    blocks ``xs [M, T, d]``, weight masks ``ws [M, T]`` (0 = pad),
    centers ``cs [M, k, d]``, active-center masks ``kmask [M, k]`` (a
    meso wanting fewer than ``k`` centers masks the rest — inactive
    slots are pinned far away so they never attract rows nor re-seed).
    One batched einsum assigns, a one-hot MXU contraction updates, and
    with ``reseed`` each meso's smallest clusters are re-seeded by
    splitting its fattest (:func:`_split_fattest`) — the batched twin of
    one :func:`_balanced_lloyd` sweep. Returns ``(centers, re-seeded)``."""
    xf = xs.astype(jnp.float32)
    wf = ws.astype(jnp.float32)
    km = kmask.astype(jnp.bool_)
    k = cs.shape[1]
    k_active = jnp.maximum(jnp.sum(km, axis=1).astype(jnp.float32), 1.0)
    total_w = jnp.maximum(jnp.sum(wf, axis=1), 1e-12)        # [M]
    x_sq = jnp.sum(xf * xf, axis=-1)                         # [M, T]
    FAR = jnp.float32(1e15)

    cs = jnp.where(km[..., None], cs.astype(jnp.float32), FAR)  # park
    c_sq = jnp.sum(cs * cs, axis=-1)                         # [M, k]
    g = jnp.einsum("mtd,mkd->mtk", xf, cs, precision=get_precision(),
                   preferred_element_type=jnp.float32)
    d2 = jnp.maximum(x_sq[..., None] + c_sq[:, None, :] - 2.0 * g, 0.0)
    labels = jnp.argmin(d2, axis=-1)                         # [M, T]
    oh = jax.nn.one_hot(labels, k, dtype=jnp.float32) * wf[..., None]
    counts = jnp.sum(oh, axis=1)                             # [M, k]
    sums = jnp.einsum("mtk,mtd->mkd", oh, xf, precision=get_precision(),
                      preferred_element_type=jnp.float32)
    new_c = jnp.where(counts[..., None] > 0,
                      sums / jnp.maximum(counts[..., None], 1e-12), cs)
    keys = jax.random.split(key, cs.shape[0])
    member = jax.vmap(_member_rows, in_axes=(0, 0, None, 0))(
        labels, wf, k, keys)                                 # [M, k]
    rows = jnp.take_along_axis(xf, member[..., None], axis=1)  # [M, k, d]
    step = _SPLIT_SCALE * (rows - new_c)
    new_c, n = jax.vmap(_split_fattest)(new_c, counts, km & reseed, step,
                                        total_w / k_active)
    return new_c, jnp.sum(n)


def _balanced_lloyd_batched(xs, ws, c0s, kmask, n_iters: int, key):
    """All mesoclusters' fine Lloyd fits: ``n_iters`` sweeps of
    :func:`_balanced_sweep_batched`, one compiled program for every
    meso (a per-meso loop would compile one program per distinct
    (size, k)); every sweep but the last re-seeds. Returns
    ``(centers, clusters re-seeded)``, the count on the device.

    The sweeps are dispatched one program each, not chained in one
    ``fori_loop``: on a TPU v5e (JAX 0.9.0, libtpu 0.0.34) a program
    holding two or more of these sweeps, looped or unrolled, computed
    other centers than the same sweeps run one program at a time.
    Dispatch is asynchronous, so the host never waits between
    sweeps."""
    cs = c0s.astype(jnp.float32)
    total = jnp.int32(0)
    for i in range(n_iters):
        cs, n = _balanced_sweep_batched(xs, ws, cs, kmask,
                                        jax.random.fold_in(key, i),
                                        i < n_iters - 1)
        total = total + n
    return cs, total


def _note_reseeded(level: str, n) -> None:
    """Count the clusters a fit level re-seeded under the obs counter
    ``kmeans_balanced.reseeded{level=...}``; recorded only when obs is
    on (reading the count waits for the fit)."""
    from raft_tpu.obs import spans as _obs_spans

    if _obs_spans.enabled():
        _obs_spans.registry().inc("kmeans_balanced.reseeded", float(n),
                                  labels={"level": level})


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
    centers, n_re = _balanced_lloyd(xn, w, c0, n_clusters, params.n_iters,
                                    key)
    _note_reseeded("flat", n_re)
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
        centers, n_re = _balanced_lloyd(xn, w, c0, n_clusters,
                                        params.n_iters, key)
        _note_reseeded("flat", n_re)
        return _maybe_normalize(centers, params.metric)

    # level 1: mesoclusters (reference: detail/kmeans_balanced.cuh:758)
    w = jnp.ones((n,), jnp.float32)
    meso_c0 = init_random(key, xn, n_meso)
    meso_centers, n_re = _balanced_lloyd(xn, w, meso_c0, n_meso,
                                         params.n_iters, key)
    _note_reseeded("meso", n_re)
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
    cms, n_re = _balanced_lloyd_batched(
        subs, masks, c0s, jnp.asarray(kmask_h), params.n_iters,
        jax.random.fold_in(key, 1))
    cms = np.asarray(cms)
    _note_reseeded("fine", n_re)
    fine_centers = [cms[m, :int(k_active[m])]
                    for m in range(n_meso) if sizes[m] > 0]
    centers = jnp.asarray(np.concatenate(fine_centers, axis=0))
    if centers.shape[0] < n_clusters:  # lost slots to empty mesoclusters
        extra = init_random(jax.random.fold_in(key, 999), xn,
                            n_clusters - centers.shape[0])
        centers = jnp.concatenate([centers, extra], axis=0)

    # final joint sweeps over the full data: the fine fits balanced each
    # meso alone; these balance the lists across mesos, and the last
    # sweep settles every center on the mean of its rows
    sweeps = max(2, params.n_iters // 4) + 2
    centers, n_re = _balanced_lloyd(xn, w, centers, n_clusters, sweeps,
                                    jax.random.fold_in(key, 2))
    _note_reseeded("joint", n_re)
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
