"""select_k — batched top-k selection, the primitive gating every ANN search.

TPU-native counterpart of ``raft::matrix::select_k`` (matrix/select_k.cuh:81).
The reference dispatches between radix-select and warp-bitonic-sort kernels
(matrix/detail/select_k-inl.cuh:293); on TPU the equivalents are:

- ``lax.top_k`` — XLA's sort-based top-k, the robust default for any (len, k);
- a two-phase tiled top-k for very wide rows: per-tile ``lax.top_k`` then a
  merge pass over the concatenated per-tile candidates, mirroring the
  reference's per-tile select + cross-tile merge (knn_brute_force.cuh:234,276).

Selection is over rows of a ``[batch, len]`` matrix; ``select_min=True``
selects smallest values (distances), ``False`` largest (similarities).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from raft_tpu.core.tracing import traced
from raft_tpu.obs import spans as _obs_spans
from raft_tpu.ops import pallas_kernels as _pk
import jax.numpy as jnp
from jax import lax


def _top_k_signed(scores: jax.Array, k: int, select_min: bool):
    if select_min:
        neg_vals, idx = lax.top_k(-scores, k)
        return -neg_vals, idx
    return lax.top_k(scores, k)


# Pallas route thresholds: wide rows where the running-buffer kernel
# beats lax.top_k's full sort; small k keeps its extraction loop short.
_PALLAS_MIN_LEN = 8192
_PALLAS_MAX_K = 64

# Large-k tier thresholds (64 < k ≤ tile): two-phase tiled select for
# wide rows (see the dispatch comment in select_k).
_LARGE_K_TILE = 16384
_LARGE_K_MIN_LEN = 65536


@traced("raft_tpu.select_k")
def select_k(
    scores: jax.Array,
    k: int,
    select_min: bool = True,
    input_indices: Optional[jax.Array] = None,
    len_tile: Optional[int] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Select the k smallest/largest entries per row.

    Parameters
    ----------
    scores : [batch, len] array.
    k : number of entries to select per row (k <= len).
    select_min : smallest (distance) vs largest (similarity) selection.
    input_indices : optional [batch, len] int array of source ids; when given,
        returned indices are gathered from it (the reference's in-indices
        overload, used for cross-tile merges).
    len_tile : optional tile width to bound the sort size for very wide rows;
        when set and len > len_tile a two-phase per-tile select + merge runs.

    Returns
    -------
    (values, indices): both [batch, k]; indices are positions into the row
    (or entries of ``input_indices`` when provided).
    """
    batch, n = scores.shape
    if k > n:
        raise ValueError(f"k={k} > len={n}")

    # algorithm choice (the reference's choose_select_k_algorithm,
    # matrix/detail/select_k-inl.cuh:293): Pallas running-buffer kernel
    # for wide rows / small k on TPU, lax.top_k otherwise
    kernel_shape = n >= _PALLAS_MIN_LEN and k <= _PALLAS_MAX_K
    if impl is None:
        impl = "pallas" if _pk._on_tpu() and kernel_shape else "xla"
    if impl != "pallas" and kernel_shape:
        # a row the kernel serves best took another engine: say why, so
        # a kernel that gave way shows up in the fallback counter
        _obs_spans.count_fallback(
            "select_k", "off_tpu" if not _pk._on_tpu() else "impl_arg")
    if impl == "pallas":
        _obs_spans.count_dispatch("select_k", "pallas")
        from raft_tpu.ops import select_k_pallas

        vals, idx = select_k_pallas(scores, k, select_min=select_min)
        if input_indices is not None:
            idx = jnp.take_along_axis(input_indices, idx, axis=1)
        return vals, idx

    # large-k tier (the reference's radix path covers k ≤ 2048 at large
    # len, select_radix.cuh): the full-row sort's cost grows with len,
    # so tile + merge once rows are wide enough that the two-phase
    # cost (n·log(tile) + tiles·k·log(tiles·k)) wins
    if (len_tile is None and k > _PALLAS_MAX_K and n >= _LARGE_K_MIN_LEN
            and n >= 4 * _LARGE_K_TILE):
        len_tile = _LARGE_K_TILE
    if len_tile is not None and n > len_tile and n > k:
        # the tiled tier is a distinct engine — account it as such, not
        # as plain "xla" (large-k scan triage needs the distinction)
        _obs_spans.count_dispatch("select_k", "xla_tiled")
        return _select_k_tiled(scores, k, select_min, input_indices, len_tile)
    _obs_spans.count_dispatch("select_k", "xla")

    vals, idx = _top_k_signed(scores, k, select_min)
    if input_indices is not None:
        idx = jnp.take_along_axis(input_indices, idx, axis=1)
    return vals, idx


def _select_k_tiled(scores, k, select_min, input_indices, len_tile):
    """Two-phase: per-tile top-k then merge (reference: tiled select in
    knn_brute_force.cuh:234-276)."""
    batch, n = scores.shape
    pad_val = jnp.array(jnp.inf if select_min else -jnp.inf, scores.dtype)
    n_tiles = -(-n // len_tile)
    n_pad = n_tiles * len_tile - n
    padded = jnp.pad(scores, ((0, 0), (0, n_pad)), constant_values=pad_val)
    tiles = padded.reshape(batch, n_tiles, len_tile)
    kk = min(k, len_tile)
    tile_vals, tile_idx = _top_k_signed(tiles.reshape(batch * n_tiles, len_tile), kk, select_min)
    tile_vals = tile_vals.reshape(batch, n_tiles, kk)
    tile_idx = tile_idx.reshape(batch, n_tiles, kk)
    # translate per-tile positions to row positions
    tile_idx = tile_idx + (jnp.arange(n_tiles, dtype=tile_idx.dtype) * len_tile)[None, :, None]
    cand_vals = tile_vals.reshape(batch, n_tiles * kk)
    cand_idx = tile_idx.reshape(batch, n_tiles * kk)
    vals, pos = _top_k_signed(cand_vals, k, select_min)
    idx = jnp.take_along_axis(cand_idx, pos, axis=1)
    if input_indices is not None:
        idx = jnp.take_along_axis(input_indices, idx, axis=1)
    return vals, idx


def merge_parts(
    part_vals: jax.Array,
    part_idx: jax.Array,
    k: int,
    select_min: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Merge per-part top-k candidate lists into a final top-k.

    Counterpart of ``knn_merge_parts`` (neighbors/detail/knn_merge_parts.cuh):
    parts come from index chunks / shards / probes, each already holding its
    local top-k with *global* ids in ``part_idx``.

    Parameters
    ----------
    part_vals, part_idx : [n_parts, batch, k_part] candidate values and ids.

    Returns
    -------
    (values, indices): [batch, k].
    """
    n_parts, batch, k_part = part_vals.shape
    flat_vals = jnp.transpose(part_vals, (1, 0, 2)).reshape(batch, n_parts * k_part)
    flat_idx = jnp.transpose(part_idx, (1, 0, 2)).reshape(batch, n_parts * k_part)
    return select_k(flat_vals, k, select_min=select_min, input_indices=flat_idx)
