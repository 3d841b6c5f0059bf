"""Fused L2 nearest-neighbor (1-NN argmin) — kmeans' inner loop.

TPU-native counterpart of ``raft::distance::fused_l2_nn``
(distance/fused_l2_nn.cuh, detail/fused_distance_nn/): the L2 distance and
the argmin reduce are fused so the full [m, n] distance matrix is never
materialized in HBM. Here the fusion is expressed as a ``lax.scan`` over
column tiles of ``y`` with a running (min, argmin) carry — XLA fuses the
Gram matmul, epilogue, and reduction per tile; HBM cost is O(m·tile).
Also provides the masked variant (reference: distance/masked_nn.cuh).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.tracing import traced
from raft_tpu.ops import pallas_kernels as _pk
from raft_tpu.utils.precision import get_precision

# Column-tile width of the running-argmin scan: large enough to keep the MXU
# busy, small enough that m×tile stays cheap in HBM.
_DEFAULT_TILE = 4096


def _dist_block(x, yb, x_sq, yb_sq, sqrt):
    d2 = x_sq[:, None] + yb_sq[None, :] - 2.0 * lax.dot_general(
        x, yb, (((1,), (1,)), ((), ())), precision=get_precision(),
        preferred_element_type=jnp.float32)
    d2 = jnp.maximum(d2, 0.0)
    return jnp.sqrt(d2) if sqrt else d2


@traced("raft_tpu.fused_l2_nn_argmin")
def fused_l2_nn_argmin(
    x: jax.Array,
    y: jax.Array,
    sqrt: bool = False,
    tile: int = _DEFAULT_TILE,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """For each row of x, the L2 distance and index of its nearest row of y.

    Counterpart of ``fused_l2_nn``/``fused_l2_nn_min_reduce``
    (distance/fused_l2_nn.cuh). Returns (min_dists [m], argmins [m]).

    ``impl``: "pallas" | "xla" | None (auto: the Pallas kernel on TPU —
    the fusion is explicit there and ~100× the scanned XLA path — XLA
    elsewhere)."""
    m, d = x.shape
    n = y.shape[0]
    if impl is None:
        impl = "pallas" if _pk._on_tpu() else "xla"
    if impl == "pallas":
        from raft_tpu.ops import fused_l2_argmin as _pallas_argmin

        dist, idx = _pallas_argmin(x, y)
        return (jnp.sqrt(dist) if sqrt else dist), idx
    xf = x.astype(jnp.float32)
    x_sq = jnp.sum(xf * xf, axis=1)

    if n <= tile:
        dists = _dist_block(xf, y.astype(jnp.float32), x_sq,
                            jnp.sum(y.astype(jnp.float32) ** 2, axis=1), sqrt)
        return jnp.min(dists, axis=1), jnp.argmin(dists, axis=1).astype(jnp.int32)

    n_tiles = -(-n // tile)
    pad = n_tiles * tile - n
    yp = jnp.pad(y.astype(jnp.float32), ((0, pad), (0, 0)))
    y_blocks = yp.reshape(n_tiles, tile, d)
    y_sq = jnp.sum(y_blocks * y_blocks, axis=2)
    # mask out padded rows so they never win the argmin
    valid = (jnp.arange(n_tiles * tile).reshape(n_tiles, tile) < n)

    def step(carry, inp):
        best_d, best_i = carry
        yb, yb_sq, vmask, base = inp
        dblk = _dist_block(xf, yb, x_sq, yb_sq, sqrt)
        dblk = jnp.where(vmask[None, :], dblk, jnp.inf)
        blk_min = jnp.min(dblk, axis=1)
        blk_arg = jnp.argmin(dblk, axis=1).astype(jnp.int32) + base
        take = blk_min < best_d
        return (jnp.where(take, blk_min, best_d), jnp.where(take, blk_arg, best_i)), None

    init = (jnp.full((m,), jnp.inf, jnp.float32), jnp.zeros((m,), jnp.int32))
    bases = (jnp.arange(n_tiles) * tile).astype(jnp.int32)
    (best_d, best_i), _ = lax.scan(step, init, (y_blocks, y_sq, valid, bases))
    return best_d, best_i


@traced("raft_tpu.masked_l2_nn_argmin")
def masked_l2_nn_argmin(
    x: jax.Array,
    y: jax.Array,
    adj: jax.Array,
    group_idx: Optional[jax.Array] = None,
    sqrt: bool = False,
    tile: int = _DEFAULT_TILE,
) -> Tuple[jax.Array, jax.Array]:
    """Masked L2 argmin (reference: distance/masked_nn.cuh).

    ``adj`` is a [m, n_groups] boolean adjacency: row i may only match
    columns whose group is admitted. ``group_idx`` maps each y row to its
    group (default: one group per y row, i.e. adj is [m, n]).

    Tiled like :func:`fused_l2_nn_argmin` — a ``lax.scan`` over column
    tiles of ``y`` with a running (min, argmin) carry, so HBM cost is
    O(m·tile), never the full [m, n] matrix (the point of the
    reference's masked fusion, detail/masked_distance_base.cuh).
    """
    xf = x.astype(jnp.float32)
    x_sq = jnp.sum(xf * xf, axis=1)
    m = x.shape[0]
    n, d = y.shape
    if group_idx is None:
        group_idx = jnp.arange(n, dtype=jnp.int32)

    if n <= tile:
        dists = _dist_block(xf, y.astype(jnp.float32), x_sq,
                            jnp.sum(y.astype(jnp.float32) ** 2, axis=1), sqrt)
        dists = jnp.where(jnp.take(adj, group_idx, axis=1), dists, jnp.inf)
        return jnp.min(dists, axis=1), jnp.argmin(dists, axis=1).astype(jnp.int32)

    n_tiles = -(-n // tile)
    pad = n_tiles * tile - n
    yp = jnp.pad(y.astype(jnp.float32), ((0, pad), (0, 0)))
    y_blocks = yp.reshape(n_tiles, tile, d)
    y_sq = jnp.sum(y_blocks * y_blocks, axis=2)
    g_blocks = jnp.pad(group_idx.astype(jnp.int32), (0, pad)).reshape(
        n_tiles, tile)
    valid = (jnp.arange(n_tiles * tile).reshape(n_tiles, tile) < n)

    def step(carry, inp):
        best_d, best_i = carry
        yb, yb_sq, gb, vmask, base = inp
        dblk = _dist_block(xf, yb, x_sq, yb_sq, sqrt)
        mask = jnp.take(adj, gb, axis=1) & vmask[None, :]  # [m, tile]
        dblk = jnp.where(mask, dblk, jnp.inf)
        blk_min = jnp.min(dblk, axis=1)
        blk_arg = jnp.argmin(dblk, axis=1).astype(jnp.int32) + base
        take = blk_min < best_d
        return (jnp.where(take, blk_min, best_d),
                jnp.where(take, blk_arg, best_i)), None

    init = (jnp.full((m,), jnp.inf, jnp.float32), jnp.zeros((m,), jnp.int32))
    bases = (jnp.arange(n_tiles) * tile).astype(jnp.int32)
    (best_d, best_i), _ = lax.scan(
        step, init, (y_blocks, y_sq, g_blocks, valid, bases))
    return best_d, best_i
