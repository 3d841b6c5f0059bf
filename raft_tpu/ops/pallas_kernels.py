"""Pallas TPU kernels for the hot ANN primitives.

TPU-native re-implementation of the reference's load-bearing CUDA
kernels (SURVEY.md §7 "hard parts"):

- :func:`fused_l2_argmin` — fused distance + argmin over column tiles,
  the counterpart of ``fused_l2_nn`` (distance/detail/fused_l2_nn.cuh):
  one VMEM-resident pass per y-tile, MXU Gram + VPU epilogue + running
  (min, argmin) accumulated in the output block across the sequential
  grid axis — the [m, n] matrix never touches HBM.
- :func:`select_k_pallas` — batched top-k, counterpart of
  ``matrix::select_k``'s warp-sort path
  (matrix/detail/select_warpsort.cuh): a running k-buffer in VMEM is
  merged with each score tile by iterative extraction (k min+mask
  rounds per tile, all VPU work on VMEM-resident data — the TPU-shaped
  replacement for warp bitonic queues).

Both kernels run compiled on TPU and in interpreter mode elsewhere
(tests force ``interpret=True`` on CPU; dispatchers in matrix/distance
pick the XLA path off-TPU).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.tracing import annotate
from raft_tpu.obs.spans import env_tristate as _env_tristate

# Lane width constraint: last dim multiples of 128, sublanes of 8 (f32).
_LANES = 128
_SUBLANES = 8


def _pad_to(x, mult, axis, value):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _on_tpu() -> bool:
    """Whether the default backend is a TPU: the one place every dispatch
    decides between a compiled kernel and its off-TPU path. A backend
    that fails to initialise raises here rather than reading as "not a
    TPU", which would silently put every kernel in interpret mode."""
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# fused L2 argmin
# ---------------------------------------------------------------------------

def _fused_l2_argmin_kernel(x_ref, y_ref, nvalid_ref, dist_ref, idx_ref):
    """Grid = (m_tiles, n_tiles); n is the minor (sequential) axis, so the
    output block for a given m-tile is revisited across n-tiles and acts
    as the running (min, argmin) accumulator.

    Per-row scalars live as lane-broadcast [bm, 128] blocks — Mosaic's
    layout for 1-D f32 operands doesn't match XLA's, so 2-D it is; the
    host-side wrapper slices lane 0.  Row norms are computed in-kernel
    (cheap VPU work) to avoid extra 1-D operands."""
    nt = pl.program_id(1)
    bn = y_ref.shape[0]

    @pl.when(nt == 0)
    def _init():
        dist_ref[:] = jnp.full_like(dist_ref, jnp.inf)
        idx_ref[:] = jnp.zeros_like(idx_ref)

    x = x_ref[:]                       # [bm, d]
    y = y_ref[:]                       # [bn, d]
    xsq = jnp.sum(x * x, axis=1)       # [bm]
    ysq = jnp.sum(y * y, axis=1)       # [bn]
    d2 = (
        xsq[:, None]
        + ysq[None, :]
        - 2.0 * jax.lax.dot_general(
            x, y, (((1,), (1,)), ((), ())),
            # f32-exact MXU passes: bf16 default loses ~1e-3 relative,
            # enough to flip argmins (the reference kernel is fp32)
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    )
    d2 = jnp.maximum(d2, 0.0)
    # mask padded columns of the final tile
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1) + nt * bn
    d2 = jnp.where(col < nvalid_ref[0], d2, jnp.inf)

    blk_min = jnp.min(d2, axis=1)                                  # [bm]
    blk_arg = jnp.argmin(d2, axis=1).astype(jnp.int32) + nt * bn   # [bm]
    lanes = dist_ref.shape[1]
    take = blk_min < dist_ref[:, 0]
    dist_ref[:] = jnp.where(
        take[:, None], jnp.broadcast_to(blk_min[:, None], (blk_min.shape[0], lanes)),
        dist_ref[:])
    idx_ref[:] = jnp.where(
        take[:, None], jnp.broadcast_to(blk_arg[:, None], (blk_arg.shape[0], lanes)),
        idx_ref[:])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def fused_l2_argmin(x: jax.Array, y: jax.Array, bm: int = 256, bn: int = 512,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """(min squared-L2 distance, argmin) of each x row against all y rows.

    Pallas counterpart of ``fused_l2_nn`` (distance/fused_l2_nn.cuh).
    """
    m, d = x.shape
    n = y.shape[0]
    xf = _pad_to(x.astype(jnp.float32), bm, 0, 0.0)
    yf = _pad_to(y.astype(jnp.float32), bn, 0, 0.0)
    dpad = (-d) % _LANES
    if dpad:
        xf = jnp.pad(xf, ((0, 0), (0, dpad)))
        yf = jnp.pad(yf, ((0, 0), (0, dpad)))
    mp, np_ = xf.shape[0], yf.shape[0]
    nvalid = jnp.full((1,), n, jnp.int32)

    grid = (mp // bm, np_ // bn)
    dist, idx = pl.pallas_call(
        _fused_l2_argmin_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, xf.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, yf.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, _LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((mp, _LANES), jnp.int32),
        ],
        interpret=interpret,
    )(xf, yf, nvalid)
    return dist[:m, 0], idx[:m, 0]


# ---------------------------------------------------------------------------
# select_k (running top-k buffer, iterative extraction per tile)
# ---------------------------------------------------------------------------

def _select_k_kernel(scores_ref, nvalid_ref, vals_ref, idx_ref, *, k: int,
                     select_min: bool):
    """Grid = (m_tiles, len_tiles); len is the sequential minor axis.  The
    output [bm, kpad] block doubles as the running top-k buffer."""
    lt = pl.program_id(1)
    bm, bl = scores_ref.shape
    kpad = vals_ref.shape[1]
    big = jnp.inf if select_min else -jnp.inf

    @pl.when(lt == 0)
    def _init():
        vals_ref[:] = jnp.full_like(vals_ref, big)
        idx_ref[:] = jnp.full_like(idx_ref, -1)

    s = scores_ref[:]
    if not select_min:
        s = -s  # uniform ascending selection
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + lt * bl
    s = jnp.where(col < nvalid_ref[0], s, jnp.inf)

    buf_v = vals_ref[:] if select_min else jnp.where(
        jnp.isinf(vals_ref[:]), jnp.inf, -vals_ref[:])
    # combined candidate set: running buffer ++ this tile
    comb_v = jnp.concatenate([buf_v, s], axis=1)          # [bm, kpad+bl]
    comb_i = jnp.concatenate([idx_ref[:], col], axis=1)

    out_v = jnp.full((bm, kpad), jnp.inf, jnp.float32)
    out_i = jnp.full((bm, kpad), -1, jnp.int32)
    out_cols = jax.lax.broadcasted_iota(jnp.int32, (bm, kpad), 1)
    # k is static → unrolled extraction (scatter at a traced column is
    # unsupported in Mosaic; a where against the static column is)
    for j in range(k):
        mn = jnp.min(comb_v, axis=1)
        am = jnp.argmin(comb_v, axis=1)
        onehot = jax.lax.broadcasted_iota(jnp.int32, comb_v.shape, 1) == am[:, None]
        # gather-free pick: masked min over the argmin one-hot (Mosaic
        # has no general gather)
        picked_i = jnp.min(
            jnp.where(onehot, comb_i, jnp.iinfo(jnp.int32).max), axis=1)
        out_v = jnp.where(out_cols == j, mn[:, None], out_v)
        out_i = jnp.where(out_cols == j, picked_i[:, None], out_i)
        # knock out the extracted entry
        comb_v = jnp.where(onehot, jnp.inf, comb_v)
    vals_ref[:] = out_v if select_min else jnp.where(
        jnp.isinf(out_v), -jnp.inf, -out_v)
    idx_ref[:] = out_i


# ---------------------------------------------------------------------------
# grouped IVF list scan: contraction + metric epilogue + local top-k, fused
# ---------------------------------------------------------------------------

def _grouped_scan_kernel(qv_ref, data_ref, mask_ref, vals_ref, pos_ref, *,
                         kk: int, metric: str):
    """One (list, query-tile) program: [bq, d] × [d, Lp] on the MXU, the
    metric epilogue on the VPU, and a kk-round running extraction — the
    [bq, Lp] distance block lives and dies in VMEM.  Counterpart of the
    reference's fused scan+top-k kernels
    (ivf_flat_interleaved_scan-inl.cuh; ivf_pq_compute_similarity-inl.cuh
    manage_local_topk :439).  All metrics are minimized: ip keys are
    negated scores (caller restores sign)."""
    qv = qv_ref[0]                                  # [bq, dpad] f32
    data = data_ref[0].astype(jnp.float32)          # [Lp, dpad]
    mask = mask_ref[0]                              # [1, Lp]
    s = jax.lax.dot_general(
        qv, data, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)         # [bq, Lp]
    if metric == "ip":
        dist = -s
    else:
        qsq = jnp.sum(qv * qv, axis=1)              # [bq]
        nsq = jnp.sum(data * data, axis=1)          # [Lp]
        if metric == "cos":
            qn = jax.lax.rsqrt(jnp.maximum(qsq, 1e-30))
            cn = jax.lax.rsqrt(jnp.maximum(nsq, 1e-30))
            dist = 1.0 - s * qn[:, None] * cn[None, :]
        else:  # l2
            dist = jnp.maximum(qsq[:, None] + nsq[None, :] - 2.0 * s, 0.0)
    dist = dist + mask                              # [1, Lp] broadcast: +inf invalid

    bq = dist.shape[0]
    kpad = vals_ref.shape[2]
    out_v = jnp.full((bq, kpad), jnp.inf, jnp.float32)
    out_i = jnp.full((bq, kpad), -1, jnp.int32)
    out_cols = jax.lax.broadcasted_iota(jnp.int32, (bq, kpad), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    for j in range(kk):  # static unroll (see _select_k_kernel)
        mn = jnp.min(dist, axis=1)
        am = jnp.argmin(dist, axis=1)
        out_v = jnp.where(out_cols == j, mn[:, None], out_v)
        out_i = jnp.where(out_cols == j, am[:, None], out_i)
        # knock out the extracted entry for the next round
        dist = jnp.where(col == am[:, None], jnp.inf, dist)
    vals_ref[0] = out_v
    pos_ref[0] = out_i


# VMEM working-set budget for one grouped-scan program (of ~16 MB/core):
# list block [Lp, dpad] f32 + distance block [bq, Lp] f32 + small operands.
_GROUPED_VMEM_BUDGET = 12 * 1024 * 1024


def pallas_grouped_wanted(kk: int, L: int = 0, d: int = 0,
                          bq: int = 128) -> bool:
    """Dispatch: use the fused grouped-scan kernel on TPU for small kk
    (the extraction loop is kk VPU rounds) when one program's VMEM
    working set — padded list block + distance block — fits the budget;
    otherwise the XLA grouped path (which tiles freely) handles it.
    ``RAFT_TPU_PALLAS_GROUPED`` = always | never | auto (tri-state, see
    :func:`raft_tpu.obs.env_tristate`) — "on"/"always" runs interpreted
    off-TPU (tests)."""
    force = _env_tristate("RAFT_TPU_PALLAS_GROUPED")
    if force == "off" or kk > 64:
        return False
    if L and d:
        Lp = -(-L // _LANES) * _LANES
        dpad = -(-d // _LANES) * _LANES
        vmem = 4 * (Lp * dpad + bq * Lp + bq * dpad)
        if vmem > _GROUPED_VMEM_BUDGET:
            return False
    return True if force == "on" else _on_tpu()


@functools.partial(jax.jit,
                   static_argnames=("kk", "metric", "bq", "interpret"))
def grouped_scan_topk(q_gathered: jax.Array, list_data: jax.Array,
                      mask_add: jax.Array, kk: int, metric: str = "l2",
                      bq: int = 128, interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """Fused grouped IVF scan over one segment chunk.

    q_gathered [G, S, d] — each segment's queued queries (gathered by
    the caller from the segment tables, see ivf_common.segment_probes);
    list_data [G, L, d] — each segment's list block: raw vectors
    (ivf_flat) or bf16 reconstructions (ivf_pq recon cache); mask_add
    [G, L] — 0 for valid slots, +inf for padding/filtered.  Returns
    (keys [G, S, kk], pos [G, S, kk]): minimized sort keys (ip keys are
    negated scores) and in-list column positions (-1 when the slot saw
    fewer than kk valid candidates).  ``bq`` tiles the S axis; callers
    pass the segment size."""
    G, qmax, d = q_gathered.shape
    L = list_data.shape[1]
    assert metric in ("l2", "ip", "cos")
    bq = min(bq, max(_SUBLANES, qmax))
    q = _pad_to(q_gathered.astype(jnp.float32), bq, 1, 0.0)
    q = _pad_to(q, _LANES, 2, 0.0)
    data = _pad_to(list_data, _LANES, 2, 0.0)
    data = _pad_to(data, 16, 1, 0.0)  # 16 sublanes covers bf16 list data
    mask = _pad_to(mask_add.astype(jnp.float32), data.shape[1], 1, jnp.inf)
    mask = mask[:, None, :]  # [G, 1, Lp]: trailing dims match the array
    qp, Lp, dpad = q.shape[1], data.shape[1], data.shape[2]
    kpad = max(_LANES, -(-kk // _LANES) * _LANES)

    grid = (G, qp // bq)
    vals, pos = pl.pallas_call(
        functools.partial(_grouped_scan_kernel, kk=kk, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dpad), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, Lp, dpad), lambda g, j: (g, 0, 0)),
            pl.BlockSpec((1, 1, Lp), lambda g, j: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, kpad), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, bq, kpad), lambda g, j: (g, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, qp, kpad), jnp.float32),
            jax.ShapeDtypeStruct((G, qp, kpad), jnp.int32),
        ],
        interpret=interpret,
    )(q, data, mask)
    keys = vals[:, :qmax, :kk]
    posk = pos[:, :qmax, :kk]
    # positions beyond the valid candidates come back as inf keys
    posk = jnp.where(jnp.isinf(keys), -1, posk)
    return keys, posk


def _segmented_scan_kernel(seg_list_ref, qv_ref, data_ref, ids_ref,
                           keys_ref, pos_ref, *, metric: str, L: int):
    """One program per segment: the segment's [S, d] queries against its
    list's [Lp, d] block — which the pipeline DMAs straight out of the
    FULL packed array using the scalar-prefetched ``seg_list`` index
    (hot lists occupy consecutive segments, so repeated indices skip
    the copy entirely). Selection reduces the [S, Lp] distance row into
    128 STRIDED bins (bin = position mod 128, min across the L/128
    tiles): consecutive list slots land in distinct bins, so clustered
    datasets — where a query's true top-k sits in a run of consecutive
    rows — don't collapse into one bin (a per-consecutive-tile min
    measured recall 0.63 vs 0.97 for strided bins on 1M clustered
    data). The caller top-ks the [S, 128] bin table."""
    qv = qv_ref[0].astype(jnp.float32)              # [S, dpad]
    data = data_ref[0].astype(jnp.float32)          # [Lp, dpad]
    ids = ids_ref[0]                                # [1, Lp] i32
    s = jax.lax.dot_general(
        qv, data, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)         # [S, Lp]
    if metric == "ip":
        dist = -s
    else:
        qsq = jnp.sum(qv * qv, axis=1)
        nsq = jnp.sum(data * data, axis=1)
        if metric == "cos":
            qn = jax.lax.rsqrt(jnp.maximum(qsq, 1e-30))
            cn = jax.lax.rsqrt(jnp.maximum(nsq, 1e-30))
            dist = 1.0 - s * qn[:, None] * cn[None, :]
        else:  # l2
            dist = jnp.maximum(qsq[:, None] + nsq[None, :] - 2.0 * s, 0.0)
    col = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    bad = (ids < 0) | (col >= L)                    # [1→S, Lp] broadcast
    dist = jnp.where(bad, jnp.inf, dist)

    S, Lp = dist.shape
    T = Lp // _LANES
    d3 = dist.reshape(S, T, _LANES)
    t_iota = jax.lax.broadcasted_iota(jnp.int32, (S, T, _LANES), 1)
    ids3 = jnp.broadcast_to(ids.reshape(1, T, _LANES), (S, T, _LANES))
    imax = jnp.iinfo(jnp.int32).max

    def pick(dd):
        # (min, winner's GLOBAL id) per strided bin. Emitting ids here —
        # a one-hot masked min, Mosaic has no gather — is what lets the
        # caller skip the [n_seg·S, kk] pointwise id gather that
        # measured ~1 s at kk=40 on a 771K-slot scan
        mnx = jnp.min(dd, axis=1)                   # [S, 128]
        amx = jnp.argmin(dd, axis=1).astype(jnp.int32)
        win = t_iota == amx[:, None, :]
        idx = jnp.min(jnp.where(win, ids3, imax), axis=1)
        return mnx, jnp.where(jnp.isinf(mnx), -1, idx), win

    # two best per bin: one collision (two of a query's true top-k in
    # the same stride-128 bin) no longer loses a candidate
    mn1, id1, win1 = pick(d3)
    mn2, id2, _ = pick(jnp.where(win1, jnp.inf, d3))
    keys_ref[0] = jnp.concatenate([mn1, mn2], axis=1)   # [S, 256]
    pos_ref[0] = jnp.concatenate([id1, id2], axis=1)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def segmented_scan_topk(seg_list: jax.Array, qv: jax.Array,
                        packed: jax.Array, ids: jax.Array,
                        metric: str = "l2", interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Segmented grouped IVF scan with in-kernel list-block DMA.

    The XLA formulation gathers each probed list block out of HBM at
    ~20 GB/s (measured — TPU gathers don't stream); here the Pallas
    pipeline DMAs ``packed[seg_list[s]]`` per grid step at copy
    bandwidth, double-buffered against the MXU contraction.

    seg_list [n_seg] i32 — owning list per segment (scalar-prefetched);
    qv [n_seg, S, d] — per-segment queries (pad slots may repeat rows);
    packed [n_lists, L, d] — FULL padded list data; ids [n_lists, L].
    Returns (keys [n_seg, S, 256], ids [n_seg, S, 256]) — the two best
    (minimized sort key, GLOBAL candidate id) per strided bin, id -1
    invalid; callers merge with a top-k over the 256 candidates. Ids
    are resolved in-kernel from the VMEM ids row — an XLA-side
    pointwise id gather measured ~1 s at kk=40 on a 771K-slot scan.
    """
    n_seg, S, d = qv.shape
    n_lists, L = ids.shape
    assert metric in ("l2", "ip", "cos")
    qvp = _pad_to(qv.astype(jnp.float32), _LANES, 2, 0.0)
    data = _pad_to(packed, _LANES, 2, 0.0)
    # the kernel splits the list axis into (L/128, 128) strided bins, so
    # pad L to a full lane multiple (tiny-list indexes have L as small
    # as 8); padded slots carry id -1 → masked invalid
    data = _pad_to(data, _LANES, 1, 0.0)
    idsp = _pad_to(ids, data.shape[1], 1, -1)[:, None, :]  # [n_lists, 1, Lp]
    Lp, dpad = data.shape[1], data.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_seg,),
        in_specs=[
            pl.BlockSpec((1, S, dpad), lambda s, sl: (s, 0, 0)),
            pl.BlockSpec((1, Lp, dpad), lambda s, sl: (sl[s], 0, 0)),
            pl.BlockSpec((1, 1, Lp), lambda s, sl: (sl[s], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, 2 * _LANES), lambda s, sl: (s, 0, 0)),
            pl.BlockSpec((1, S, 2 * _LANES), lambda s, sl: (s, 0, 0)),
        ],
    )
    keys, pos = pl.pallas_call(
        functools.partial(_segmented_scan_kernel, metric=metric, L=L),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_seg, S, 2 * _LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_seg, S, 2 * _LANES), jnp.int32),
        ],
        interpret=interpret,
    )(seg_list.astype(jnp.int32), qvp, data, idsp)
    return keys, pos


def pallas_segmented_wanted(kk: int, L: int, d: int, S: int = 128) -> bool:
    """Dispatch for :func:`segmented_scan_topk`: needs kk ≤ 128 (two
    candidates per strided bin) and a VMEM-sized list block. Same env override
    as pallas_grouped_wanted."""
    force = _env_tristate("RAFT_TPU_PALLAS_GROUPED")
    if force == "off" or kk > _LANES:
        return False
    Lp = -(-L // _LANES) * _LANES
    dpad = -(-d // _LANES) * _LANES
    vmem = 4 * (Lp * dpad + S * Lp + S * dpad)
    if vmem > _GROUPED_VMEM_BUDGET:
        return False
    return True if force == "on" else _on_tpu()


# ---------------------------------------------------------------------------
# fused IVF-PQ LUT scan: packed codes streamed from HBM, n-bit unpack +
# ADC accumulation + 2-deep strided-bin top-k all in VMEM
# ---------------------------------------------------------------------------

# Candidates emitted per (segment, query) slot: two best per strided bin.
LUT_SCAN_BINS = 2 * _LANES
# Scoped VMEM limit of the LUT-scan kernel. Mosaic's default (16 MiB)
# is too small for the exact f32 tier: at pq_dim 64 its block-diagonal
# codebook operand alone is 8 MiB lane-padded, double-buffered, and the
# v5e compile needs ~34 MiB in all. A v5e core has 128 MiB of VMEM.
_LUT_SCAN_VMEM_LIMIT = 48 << 20


def _lut_scan_config(S: int, K: int, P: int, nb: int, Wb: int,
                     lut_dtype: str):
    """Static tiling for :func:`ivfpq_lut_scan_topk`, or ``None`` when the
    layout is unsupported.

    ``G`` — code rows per stored byte row (1 unfolded; ``128/nb`` for the
    lane-folded layout, see ``IvfPqIndex.codes_folded``). ``Sg`` —
    subspaces decoded per MXU call: the grouped block-diagonal codebook
    operand is ``[K·Sg, Sg·P]``, so ``Sg·P ≤ 128`` keeps the output
    inside one lane tile and the operand's VMEM cost (``S·K·P·Sg``
    entries total) stays bounded. ``Kc`` — codebook entries compared per
    one-hot pass (bounds the ``[rows, Kc·Sg]`` transient)."""
    if nb <= 0 or Wb % nb:
        return None
    G = Wb // nb
    # bin spreading rotates lanes by 128/G per fold group; G must divide
    # the lane count, and deep folds mean tiny pq_dim — not this kernel's
    # territory
    if G > 8 or (G & (G - 1)):
        return None
    op_bytes = 4 if lut_dtype == "float32" else 2
    cap = min(_LANES // max(P, 1),
              (4 << 20) // max(1, S * K * P * op_bytes))
    if cap < 1:
        return None
    Sg = max(d for d in range(1, min(S, cap) + 1) if S % d == 0)
    # largest power of two ≤ min(K, 2048/Sg): divides K (K = 2^pq_bits)
    Kc = 1 << (min(K, max(1, 2048 // Sg)).bit_length() - 1)
    return G, Sg, Kc


def _lane_pick(a: jax.Array, start: int, stride: int, n: int) -> jax.Array:
    """Static strided lane slice of ``a [1, W]`` → ``[1, n]``."""
    if stride == 1:
        return jax.lax.slice(a, (0, start), (1, start + n))
    return jax.lax.slice(a, (0, start),
                         (1, start + (n - 1) * stride + 1), (1, stride))


def _ref_lane_pick(ref, start: int, stride: int, n: int) -> jax.Array:
    """:func:`_lane_pick` of a ``[1, W]`` ref's row. A contiguous window
    is read from the ref itself: Mosaic rejects broadcasting a lane
    slice of a loaded single-row vector taken past lane 128."""
    if stride == 1:
        return ref[:, pl.ds(start, n)]
    return _lane_pick(ref[:], start, stride, n)


def _roll_lanes(x: jax.Array, sh: int) -> jax.Array:
    """Static lane rotate (lane i ← lane (i − sh) mod W) via two slices —
    unambiguous in both Mosaic and interpret mode (``pltpu.roll``'s
    interpret path is ``jnp.roll``; its Mosaic path is tpu.dynamic_rotate,
    and relying on both agreeing is exactly the kind of bet this kernel
    avoids)."""
    sh %= x.shape[1]
    if sh == 0:
        return x
    return jnp.concatenate([x[:, -sh:], x[:, :-sh]], axis=1)


def _lut_unpack_codes(bytes_f, sel_lo, sel_hi, off_row, pq_bits: int,
                      K: int):
    """In-kernel unpack_bits: stored byte rows → integer code values via
    the exact f32 selection matmuls (Mosaic has no lane gather) plus
    integer shift/mask. ``bytes_f`` [Rt, Wb] f32; returns [Rt, G·S]
    i32. Shared by the standalone LUT-scan kernel and the fused
    scan-in-ring kernel."""
    lo = jax.lax.dot_general(
        bytes_f, sel_lo, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # [Rt, G·S]
    if pq_bits == 8:
        return lo.astype(jnp.int32)
    hi = jax.lax.dot_general(
        bytes_f, sel_hi, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    v16 = lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 8)
    return jax.lax.shift_right_logical(v16, off_row) & (K - 1)


def _filter_unpack_operands(n_lanes: int):
    """Byte-column selection matrix + per-lane shift row unpacking one
    tile's PACKED filter bytes (``sample_filter.pack_mask_bytes``
    layout: bit j of byte b = candidate position 8·b + j) to per-lane
    keep bits — the filter's instance of the n-bit code unpack
    machinery (:func:`_lut_unpack_codes`): byte values are ≤ 255 so the
    f32 selection matmul is exact, then integer shift/mask."""
    sel = np.zeros((n_lanes // 8, n_lanes), np.float32)
    lanes = np.arange(n_lanes)
    sel[lanes // 8, lanes] = 1.0
    off = jnp.asarray((lanes % 8).astype(np.int32)[None, :])
    return jnp.asarray(sel), off


def _filter_vmem_bytes(G: int, Rt: int) -> int:
    """VMEM cost of one tier's in-kernel filter operands — the byte
    slots (double-buffered), the unpack selection matrix, and the
    shift row + unpacked keep bits (:func:`_filter_unpack_operands` /
    :func:`_lut_unpack_filter`). The ONE model both admission gates
    (``pallas_lut_scan_wanted``, ``ring_lut_scan_kernel_ok``) consult,
    so a layout change cannot leave one gate with a stale budget."""
    lanes = G * Rt
    return (2 * max(lanes // 8, _LANES)   # filter byte slots
            + (lanes // 8) * lanes * 4    # unpack selection matrix
            + 2 * lanes * 4)              # shift row + keep bits


def _lut_unpack_filter(fbytes_f, fsel, foff):
    """``fbytes_f`` [1, n_lanes/8] f32 byte values → [1, n_lanes] i32
    keep bits (1 = candidate may be returned). Shared by the standalone
    LUT-scan kernel and the fused scan-in-ring kernel."""
    b = jax.lax.dot_general(
        fbytes_f, fsel, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # [1, n_lanes]
    return jax.lax.shift_right_logical(b.astype(jnp.int32), foff) & 1


def _lut_tile_update(code, qv, qc, ids_ref, norms_ref, cbp_ref, t,
                     state, *, metric: str, pq_bits: int, S: int,
                     P: int, G: int, Sg: int, Kc: int, L: int, Rt: int,
                     rot: int, rotp: int, exact: bool, key_bias=None,
                     filt_row=None):
    """One code tile's ADC + 2-deep strided-bin update — the shared
    compute body of the LUT scan (steps 3–4 of
    :func:`_ivfpq_lut_scan_kernel`'s docstring), factored so the fused
    scan-in-ring kernel runs the identical math per tile.

    ``code`` [Rt, G·S] i32 unpacked code values; ``qv`` [rows, rotp]
    f32 rotated queries; ``qc`` [rows] ⟨q, c⟩; ``ids_ref``/``norms_ref``
    [1, G·Rt] refs of the tile's id / norm rows; ``cbp_ref`` the grouped
    block-diagonal codebook operand (indexable per subspace group); ``t`` the code-tile index within
    the list (traced or static); ``state`` = (b1k, b1i, b2k, b2i)
    running 2-deep bin values; ``key_bias`` an optional [rows, 1]
    additive key column (the fused ring mode's per-query probe mask —
    un-probed rows get +``_LUT_MASK_BIG``); ``filt_row`` an optional
    [1, G·Rt] i32 keep-bit row (:func:`_lut_unpack_filter`) — filtered
    candidates join the invalid-id lanes in the ±inf/-1 sentinel
    epilogue, the exact pattern GL13 polices. Returns the updated
    state."""
    rows = qv.shape[0]
    n_sg = S // Sg
    slabs = Rt // _LANES
    K = 1 << pq_bits
    opd = jnp.float32 if exact else jnp.bfloat16
    prec = (jax.lax.Precision.HIGHEST if exact
            else jax.lax.Precision.DEFAULT)
    b1k, b1i, b2k, b2i = state
    for si in range(slabs):
        for g in range(G):
            # decode this slab's fold group in VMEM: [128, rot]
            parts = []
            for sg in range(n_sg):
                cs = jax.lax.slice(
                    code, (si * _LANES, g * S + sg * Sg),
                    ((si + 1) * _LANES, g * S + (sg + 1) * Sg))
                tiled = cs
                for _ in range(Kc.bit_length() - 1):
                    tiled = jnp.concatenate([tiled, tiled], axis=1)
                acc = jnp.zeros((_LANES, Sg * P), jnp.float32)
                for kc in range(K // Kc):
                    kidx = (jax.lax.broadcasted_iota(
                        jnp.int32, (_LANES, Kc * Sg), 1) // Sg + kc * Kc)
                    # one-hot built in f32 then narrowed: Mosaic cannot
                    # relayout an i1 mask straight into packed 16-bit
                    oh = jnp.where(tiled == kidx, 1.0, 0.0).astype(opd)
                    cbp = jax.lax.slice(
                        cbp_ref[sg], (kc * Kc * Sg, 0),
                        ((kc + 1) * Kc * Sg, Sg * P))
                    acc = acc + jax.lax.dot_general(
                        oh, cbp, (((1,), (0,)), ((), ())),
                        precision=prec,
                        preferred_element_type=jnp.float32)
                parts.append(acc)
            if rotp > rot:
                parts.append(jnp.zeros((_LANES, rotp - rot), jnp.float32))
            dec = jnp.concatenate(parts, axis=1)     # [128, rotp]
            qd = jax.lax.dot_general(
                qv, dec, (((1,), (1,)), ((), ())),
                precision=prec,
                preferred_element_type=jnp.float32)  # [rows, 128] ⟨q, d⟩
            lane0 = G * si * _LANES + g
            ids_g = _ref_lane_pick(ids_ref, lane0, G, _LANES)   # [1, 128]
            # list position of lane r: G·(t·Rt + si·128 + r) + g — OOB
            # tail lanes of the last tile carry garbage, mask them
            l_pos = (t * Rt + si * _LANES) * G + g \
                + G * jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
            valid = (ids_g >= 0) & (l_pos < L)
            if filt_row is not None:
                keep = _lane_pick(filt_row, lane0, G, _LANES)
                valid = jnp.logical_and(valid, keep > 0)
            if metric == "ip":
                key = -(qc[:, None] + qd)
            else:  # l2: ‖c+d‖² − 2⟨q, c+d⟩ (caller adds ‖q‖²)
                norms_g = _ref_lane_pick(norms_ref, lane0, G, _LANES)
                key = norms_g - 2.0 * (qc[:, None] + qd)
            key = jnp.where(valid, key, jnp.inf)
            if key_bias is not None:
                key = key + key_bias
            idv = jnp.broadcast_to(jnp.where(valid, ids_g, -1),
                                   (rows, _LANES))
            # spread fold groups across bins: lane rotate by g·(128/G)
            sh = g * (_LANES // G)
            kn = _roll_lanes(key, sh)
            inew = _roll_lanes(idv, sh)
            # 2-deep running bin merge
            lt1 = kn < b1k
            lt2 = jnp.logical_and(jnp.logical_not(lt1), kn < b2k)
            b2k = jnp.where(lt1, b1k, jnp.where(lt2, kn, b2k))
            b2i = jnp.where(lt1, b1i, jnp.where(lt2, inew, b2i))
            b1k = jnp.where(lt1, kn, b1k)
            b1i = jnp.where(lt1, inew, b1i)
    return b1k, b1i, b2k, b2i


def _lut_scan_operands(codebooks: jax.Array, pq_bits: int, nb: int,
                       Wb: int, G: int, Sg: int, lut_dtype: str):
    """Host-side operand prep shared by the standalone LUT scan and the
    fused scan-in-ring kernel: the byte-column selection matrices +
    per-column shift row feeding :func:`_lut_unpack_codes`, and the
    grouped block-diagonal codebook operand feeding
    :func:`_lut_tile_update` (``cbp[gi, k·Sg + j, j·P : (j+1)·P] =
    cb[gi·Sg + j, k]`` — the one-hot's lane order is k-major, then j).
    One construction site keeps the two kernels' operands bit-identical
    — the fused tier's exact-parity contract with the standalone tier
    rides on it. Returns (sel_lo, sel_hi, off_arr, cbp)."""
    S, K, P = codebooks.shape
    s_idx = np.arange(S)
    byte_idx = (s_idx * pq_bits) // 8
    off_np = ((s_idx * pq_bits) % 8).astype(np.int32)
    sel_lo = np.zeros((Wb, G * S), np.float32)
    sel_hi = np.zeros((Wb, G * S), np.float32)
    for g in range(G):
        for s in range(S):
            sel_lo[g * nb + byte_idx[s], g * S + s] = 1.0
            if byte_idx[s] + 1 < nb:
                sel_hi[g * nb + byte_idx[s] + 1, g * S + s] = 1.0
    off_arr = jnp.asarray(np.tile(off_np, G)[None, :])
    opd = jnp.float32 if lut_dtype == "float32" else jnp.bfloat16
    cb = codebooks.astype(jnp.float32)
    if lut_dtype == "float8_e4m3":
        cb = cb.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    n_sg = S // Sg
    cb_t = cb.reshape(n_sg, Sg, K, P).transpose(0, 2, 1, 3)
    eye = jnp.eye(Sg, dtype=jnp.float32)
    cbp = (cb_t.astype(jnp.float32)[:, :, :, None, :]
           * eye[None, None, :, :, None]).reshape(
               n_sg, K * Sg, Sg * P).astype(opd)
    return jnp.asarray(sel_lo), jnp.asarray(sel_hi), off_arr, cbp


def _ivfpq_lut_scan_kernel(seg_list_ref, qv_ref, codes_ref, ids_ref,
                           norms_ref, ctr_ref, sel_lo_ref, sel_hi_ref,
                           off_ref, cbp_ref, *rest,
                           metric: str, pq_bits: int, S: int, P: int,
                           G: int, Sg: int, Kc: int, L: int, Rt: int,
                           rot: int, exact: bool, filtered: bool):
    """One (segment, code-tile) program of the fused IVF-PQ scan.

    Grid = (n_seg, n_tiles); the tile axis is the sequential minor axis,
    so the ``[seg, 2·128]`` output block is the running 2-deep bin buffer
    (same revisit pattern as ``_select_k_kernel``). Per step:

    1. the pipeline DMAs the owning list's next ``[Rt, Wb]`` block of
       PACKED u8 codes straight out of the full (possibly lane-folded)
       array via the scalar-prefetched ``seg_list`` index;
    2. bytes → code values with integer shifts/masks; the byte columns
       feeding each (fold-group, subspace) are picked by one exact f32
       selection matmul (Mosaic has no lane gather — a 0/1 matrix on the
       MXU is the TPU idiom for a static permutation);
    3. ADC accumulation Σ_s QLUT[s, code_s] in its MXU-factorized form:
       QLUT[s, k] = ⟨q_s, cb[s,k]⟩, so Σ_s QLUT[s, code_s] =
       ⟨q_rot, decoded⟩ with decoded built in VMEM by a grouped
       block-diagonal one-hot × codebook matmul (``[Rt, Kc·Sg] ×
       [Kc·Sg, Sg·P]``) — identical math to the reference's fused LUT
       gather (ivf_pq_compute_similarity-inl.cuh) with the per-code
       gather replaced by the one-hot contraction, and the decoded block
       never leaves VMEM (contrast: the XLA grouped path round-trips a
       decoded f32 chunk through HBM per segment chunk);
    4. metric epilogue against the streamed f32 norms + the in-kernel
       ⟨q, c⟩ term, then a 2-deep strided-bin running min with GLOBAL
       candidate ids (fold groups rotate lanes by 128/G so consecutive
       code rows land in distinct bins — see _segmented_scan_kernel's
       clustered-data note).

    ``filtered`` mode streams the list's PACKED per-candidate filter
    bytes (``sample_filter.list_filter_bytes``) alongside the codes —
    the same per-tile DMA pattern as the ids/norms rows — unpacks them
    in-kernel with the code-unpack shift/mask machinery
    (:func:`_lut_unpack_filter`), and masks filtered candidates to the
    +inf/-1 sentinel in the bin epilogue, exactly as invalid ids.
    """
    t = pl.program_id(1)
    if filtered:
        fbits_ref, fsel_ref, foff_ref, keys_ref, oids_ref = rest
    else:
        keys_ref, oids_ref = rest
        fbits_ref = fsel_ref = foff_ref = None
    seg = qv_ref.shape[1]
    K = 1 << pq_bits
    rotp = qv_ref.shape[2]

    @pl.when(t == 0)
    def _init():
        keys_ref[:] = jnp.full_like(keys_ref, jnp.inf)
        oids_ref[:] = jnp.full_like(oids_ref, -1)

    qv = qv_ref[0]                                   # [seg, rotp] f32
    ctr = ctr_ref[0]                                 # [1, rotp] f32
    qc = jnp.sum(qv * ctr, axis=1)                   # [seg] ⟨q, c⟩

    # bytes → code values: selection matmul (exact: values ≤ 255 in f32)
    # then integer shift/mask — the in-kernel unpack_bits
    bytes_f = codes_ref[0].astype(jnp.int32).astype(jnp.float32)
    code = _lut_unpack_codes(bytes_f, sel_lo_ref[:], sel_hi_ref[:],
                             off_ref[:], pq_bits, K)
    filt_row = None
    if filtered:
        fb_f = fbits_ref[0, 0].astype(jnp.int32).astype(jnp.float32)
        filt_row = _lut_unpack_filter(fb_f, fsel_ref[:], foff_ref[:])

    cur_k = keys_ref[0]                              # [seg, 256]
    cur_i = oids_ref[0]
    state = (jax.lax.slice(cur_k, (0, 0), (seg, _LANES)),
             jax.lax.slice(cur_i, (0, 0), (seg, _LANES)),
             jax.lax.slice(cur_k, (0, _LANES), (seg, 2 * _LANES)),
             jax.lax.slice(cur_i, (0, _LANES), (seg, 2 * _LANES)))
    b1k, b1i, b2k, b2i = _lut_tile_update(
        code, qv, qc, ids_ref.at[0], norms_ref.at[0], cbp_ref, t, state,
        metric=metric, pq_bits=pq_bits, S=S, P=P, G=G, Sg=Sg, Kc=Kc,
        L=L, Rt=Rt, rot=rot, rotp=rotp, exact=exact, filt_row=filt_row)
    keys_ref[0] = jnp.concatenate([b1k, b2k], axis=1)
    oids_ref[0] = jnp.concatenate([b1i, b2i], axis=1)


@functools.partial(jax.jit, static_argnames=(
    "metric", "pq_bits", "pq_dim", "L", "lut_dtype", "interpret"))
def ivfpq_lut_scan_topk(seg_list: jax.Array, qv: jax.Array,
                        packed: jax.Array, ids: jax.Array,
                        norms: jax.Array, centers_rot: jax.Array,
                        codebooks: jax.Array, metric: str = "l2", *,
                        pq_bits: int, pq_dim: int, L: int,
                        lut_dtype: str = "float32",
                        filter_bytes=None,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Fused segmented IVF-PQ scan over PACKED codes (no recon cache).

    The oversampled DEEP-100M configs (n_probes 64–128, k_cand 400–1000)
    are hostile to the XLA grouped scan twice over: the decoded-f32 list
    chunks and the ``[n_seg, seg, k_cand]`` accumulators round-trip HBM
    (measured OOM at QB=2000 beside a 10.9 GB index), and the per-chunk
    one-hot decode re-materializes. This kernel streams the packed
    (optionally lane-folded) u8 codes per segment via scalar-prefetch
    DMA, unpacks ``pq_bits`` in-kernel, performs the ADC accumulation
    Σ_s QLUT[s, code_s] on-chip in its MXU-factorized form, and keeps a
    2-deep strided-bin top buffer per (segment, query) slot — nothing
    but the ``[n_seg, seg, 256]`` bin tables ever reaches HBM.

    seg_list [n_seg] i32 — owning list per segment (scalar-prefetched);
    qv [n_seg, seg, rot_dim] f32 — per-segment ROTATED queries;
    packed [n_lists, R, Wb] u8 — packed codes, native storage layout
    (``Wb = nb`` unfolded, ``Wb = 128`` lane-folded);
    ids / norms [n_lists, L] — global ids (-1 pad) and ‖c+d‖²;
    centers_rot [n_lists, rot_dim] f32; codebooks [S, K, P] f32
    (per_subspace only).

    ``lut_dtype`` is the reference's ``search_params::lut_dtype`` trade
    (ivf_pq_fp_8bit.cuh) mapped to TPU: it sets the dtype of the
    codebook operand and the one-hot/scan contraction ("float32" = exact
    f32 MXU passes, "bfloat16" = bf16 operands, "float8_e4m3" = fp8-
    quantized codebook values contracted in bf16). The XLA path
    quantizes the LUT entries ⟨q_s, cb[s,k]⟩ instead — same knob, same
    footprint trade, numerically a sibling rather than a twin.

    ``filter_bytes`` [n_lists, ceil(L/8)] u8 — optional per-candidate
    packed filter mask (``sample_filter.list_filter_bytes``): the words
    of the caller's ``filter_bitset`` re-packed to the per-list slot
    layout so the kernel streams them HBM→VMEM per code tile alongside
    the codes (1 bit/candidate — 32× less traffic than an f32 bias
    row), unpacks them with the code-unpack shift/mask machinery, and
    masks filtered candidates to the +inf/-1 sentinel at the bin
    epilogue. With a filter the emitted bins hold only KEPT candidates,
    so a selective filter no longer makes kept neighbors unreachable —
    the reason filtered searches used to be banned from this tier.

    Returns (keys [n_seg, seg, 256], ids [n_seg, seg, 256]): minimized
    sort keys per strided bin (l2: ‖c+d‖² − 2⟨q,c+d⟩, add ‖q‖²; ip:
    −⟨q,c+d⟩) and GLOBAL candidate ids (-1 invalid), two best per bin —
    merge like ``segmented_scan_topk``'s output.
    """
    n_seg, seg, rot = qv.shape
    S, K, P = codebooks.shape
    assert metric in ("l2", "ip")
    assert S == pq_dim and K == (1 << pq_bits)
    nb = (S * pq_bits + 7) // 8
    Wb = packed.shape[2]
    cfg = _lut_scan_config(S, K, P, nb, Wb, lut_dtype)
    if cfg is None:
        raise ValueError(
            f"unsupported packed-code layout for the LUT scan kernel: "
            f"nb={nb} Wb={Wb} (gate with pallas_lut_scan_wanted)")
    G, Sg, Kc = cfg
    exact = lut_dtype == "float32"

    R = packed.shape[1]
    Rt = 2 * _LANES if R >= 2 * _LANES else _LANES
    if R < Rt:  # tiny index (tests): pad to one full tile
        packed = _pad_to(packed, Rt, 1, 0)
        ids = _pad_to(ids, G * Rt, 1, -1)
        norms = _pad_to(norms, G * Rt, 1, 0.0)
    n_t = -(-packed.shape[1] // Rt)
    filtered = filter_bytes is not None
    Fbt = G * Rt // 8
    if filtered:
        # pad to WHOLE tiles (0 = filtered): ids/norms tolerate the
        # pipeline's OOB tail because garbage lanes are masked, but a
        # misread KEEP bit would admit a filtered candidate. One
        # [1, Fbt] row per tile: a block's last two dims must tile
        # (8, 128) or span the array's, and Fbt < 128
        fbits = _pad_to(filter_bytes, n_t * Fbt, 1, 0).reshape(
            filter_bytes.shape[0], n_t, 1, Fbt)

    qvp = _pad_to(qv.astype(jnp.float32), _SUBLANES, 1, 0.0)
    qvp = _pad_to(qvp, _LANES, 2, 0.0)
    segp, rotp = qvp.shape[1], qvp.shape[2]
    ctr = _pad_to(centers_rot.astype(jnp.float32), _LANES, 1, 0.0)
    # per-list rows ride a unit middle axis: Mosaic needs a block's last
    # two dims to tile (8, 128) or span the array's, and one list row
    # per step is a (1, ·) block
    ids = ids[:, None, :]
    norms = norms[:, None, :]
    ctr = ctr[:, None, :]

    sel_lo, sel_hi, off_arr, cbp = _lut_scan_operands(
        codebooks, pq_bits, nb, Wb, G, Sg, lut_dtype)
    n_sg = S // Sg

    in_specs = [
        pl.BlockSpec((1, segp, rotp), lambda s, t, sl: (s, 0, 0)),
        pl.BlockSpec((1, Rt, Wb), lambda s, t, sl: (sl[s], t, 0)),
        pl.BlockSpec((1, 1, G * Rt), lambda s, t, sl: (sl[s], 0, t)),
        pl.BlockSpec((1, 1, G * Rt), lambda s, t, sl: (sl[s], 0, t)),
        pl.BlockSpec((1, 1, rotp), lambda s, t, sl: (sl[s], 0, 0)),
        pl.BlockSpec((Wb, G * S), lambda s, t, sl: (0, 0)),
        pl.BlockSpec((Wb, G * S), lambda s, t, sl: (0, 0)),
        pl.BlockSpec((1, G * S), lambda s, t, sl: (0, 0)),
        pl.BlockSpec((n_sg, K * Sg, Sg * P),
                     lambda s, t, sl: (0, 0, 0)),
    ]
    operands = [qvp, packed, ids, norms, ctr, sel_lo, sel_hi, off_arr,
                cbp]
    if filtered:
        fsel, foff = _filter_unpack_operands(G * Rt)
        in_specs += [
            pl.BlockSpec((1, 1, 1, Fbt), lambda s, t, sl: (sl[s], t, 0, 0)),
            pl.BlockSpec((Fbt, G * Rt), lambda s, t, sl: (0, 0)),
            pl.BlockSpec((1, G * Rt), lambda s, t, sl: (0, 0)),
        ]
        operands += [fbits, fsel, foff]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_seg, n_t),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, segp, LUT_SCAN_BINS),
                         lambda s, t, sl: (s, 0, 0)),
            pl.BlockSpec((1, segp, LUT_SCAN_BINS),
                         lambda s, t, sl: (s, 0, 0)),
        ],
    )
    keys, kids = pl.pallas_call(
        functools.partial(
            _ivfpq_lut_scan_kernel, metric=metric, pq_bits=pq_bits, S=S,
            P=P, G=G, Sg=Sg, Kc=Kc, L=L, Rt=Rt, rot=rot, exact=exact,
            filtered=filtered),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_seg, segp, LUT_SCAN_BINS), jnp.float32),
            jax.ShapeDtypeStruct((n_seg, segp, LUT_SCAN_BINS), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LUT_SCAN_VMEM_LIMIT),
        interpret=interpret,
    )(seg_list.astype(jnp.int32), *operands)
    return keys[:, :seg], kids[:, :seg]


def pallas_lut_scan_wanted(S: int, K: int, P: int, nb: int, Wb: int,
                           L: int, rot: int, seg: int = 128,
                           lut_dtype: str = "float32",
                           filtered: bool = False) -> bool:
    """Dispatch for :func:`ivfpq_lut_scan_topk` — the ``scan_select=
    "pallas"`` tier. Needs a per_subspace packed layout the in-kernel
    unpack supports (byte width dividing the stored lane width, fold
    group ≤ 8) and a VMEM-sized working set (``filtered`` adds the
    filter-byte stream + its unpack selection matrix; the HBM side of
    a filtered dispatch is ``ivf_common.filtered_scan_mem_ok``'s job).
    Env override ``RAFT_TPU_PALLAS_LUTSCAN`` = always | never | auto
    (tri-state, see :func:`raft_tpu.obs.env_tristate`) — "on"/"always"
    runs interpreted off-TPU (tests)."""
    force = _env_tristate("RAFT_TPU_PALLAS_LUTSCAN")
    if force == "off":
        return False
    cfg = _lut_scan_config(S, K, P, nb, Wb, lut_dtype)
    if cfg is None:
        return False
    G, Sg, Kc = cfg
    op_bytes = 4 if lut_dtype == "float32" else 2
    rotp = -(-rot // _LANES) * _LANES
    Rt = 2 * _LANES
    vmem_f = _filter_vmem_bytes(G, Rt) if filtered else 0
    vmem = vmem_f + (
        2 * seg * rotp * 4            # qv block (+double buffer)
        + 2 * Rt * max(Wb, _LANES)    # u8 codes block
        + Rt * G * S * 8              # unpacked bytes + codes (f32+i32)
        + S * K * P * Sg * op_bytes   # grouped block-diag codebooks
        + _LANES * Kc * Sg * 8        # one-hot transient (+tiled codes)
        + _LANES * rotp * 4           # decoded block
        + seg * _LANES * 4            # qd block
        + 2 * seg * LUT_SCAN_BINS * 8  # running bin buffers (keys+ids)
        + 2 * Wb * G * S * 4          # selection matrices
    )
    if vmem > _GROUPED_VMEM_BUDGET:
        return False
    return True if force == "on" else _on_tpu()


def _extract_topk_block(comb_v: jax.Array, comb_i: jax.Array, k: int,
                        kpad: int) -> Tuple[jax.Array, jax.Array]:
    """k-round extraction merge of a combined candidate block: reduce
    ``comb_v``/``comb_i`` [rows, C] (minimized keys, +inf = empty slot)
    to the ascending top-k in a [rows, kpad] lane tile, ids resolved
    gather-free via the argmin one-hot (Mosaic has no general gather).
    The in-kernel merge shared by the fused gather-refine and the ring
    top-k exchange — k is static, so the loop unrolls to k VPU rounds."""
    rows = comb_v.shape[0]
    out_cols = jax.lax.broadcasted_iota(jnp.int32, (rows, kpad), 1)
    # sentinel init anchored on the candidate block rather than two bare
    # jnp.full broadcasts: XLA CPU's sharding propagation aborts on a
    # pair of broadcasted-constant stores in a discharged (interpret)
    # kernel that also issued a remote DMA — the predicate is constant-
    # false, so the values are identical
    out_v = jnp.where(out_cols < 0, comb_v[:, :kpad], jnp.inf)
    out_i = jnp.where(out_cols < 0, comb_i[:, :kpad], -1)
    imax = jnp.iinfo(jnp.int32).max
    for j in range(k):  # static unroll (see _select_k_kernel)
        mn = jnp.min(comb_v, axis=1)
        am = jnp.argmin(comb_v, axis=1)
        onehot = jax.lax.broadcasted_iota(
            jnp.int32, comb_v.shape, 1) == am[:, None]
        picked = jnp.min(jnp.where(onehot, comb_i, imax), axis=1)
        picked = jnp.where(jnp.isinf(mn), -1, picked)
        out_v = jnp.where(out_cols == j, mn[:, None], out_v)
        out_i = jnp.where(out_cols == j, picked[:, None], out_i)
        comb_v = jnp.where(onehot, jnp.inf, comb_v)
    return out_v, out_i


# ---------------------------------------------------------------------------
# fused gather-refine: per-query candidate rows streamed HBM→VMEM by id,
# exact distance epilogue + running top-k on-chip — the [m, C, d] gather
# buffer never exists
# ---------------------------------------------------------------------------

# Queries per program (one f32 sublane tile) and candidates gathered per
# sequential step (one lane tile).
GATHER_REFINE_BQ = 8
GATHER_REFINE_BC = 128
# Candidate-row DMAs kept in flight per program (row gathers are the
# bottleneck — ~512 B each — so the queue depth is what hides their
# issue latency behind the copy engine).
_GATHER_NBUF = 8
# In-kernel running-buffer width (one lane tile); the k-round merge
# extraction bounds serviceable k the same way _select_k_kernel does.
GATHER_REFINE_MAX_K = 64
# Stage scope of every re-rank program (op_name metadata only), this
# kernel's and the XLA re-rank's in neighbors/refine.py.
REFINE_SCOPE = "raft_tpu.refine"


def _gather_refine_kernel(q_ref, cand_ref, cand_hbm, data_hbm,
                          *rest, k: int, metric: str,
                          n_rows: int, filtered: bool):
    """One (query-tile, candidate-tile) program of the fused refine.

    Grid = (m_tiles, c_tiles); the candidate axis is the sequential
    minor axis, so the ``[bq, kpad]`` output block is the running top-k
    buffer (same revisit pattern as ``_select_k_kernel``). Per step:

    1. the tile's candidate ids are DMA'd HBM→SMEM (DMA row addresses
       must be scalar-readable — a VMEM operand cannot index an HBM
       ref);
    2. each candidate's dataset row streams HBM→VMEM through its own
       row DMA, ``_GATHER_NBUF`` in flight — the counterpart of
       ``refine_device.cuh``'s per-candidate global loads, and the step
       that replaces the XLA path's materialized ``[m, C, d]`` gather;
    3. exact distance epilogue on the VPU (all metrics minimized: ip
       keys are negated scores, cosine keys are 1 − cos; invalid ids
       masked to +inf) and a k-round merge of (running buffer ++ tile)
       by iterative extraction, ids resolved gather-free via the
       argmin one-hot.

    ``filtered`` mode rides the same row-DMA queue: each candidate's
    bitset WORD (its id is already scalar-readable in SMEM — the same
    address source the row DMA uses) streams HBM→VMEM through a
    parallel ``_GATHER_NBUF``-deep queue, and the metric epilogue
    poisons rows whose bit is clear to the +inf/-1 sentinel, exactly
    as invalid ids.
    """
    if filtered:
        (filt_hbm, vals_ref, ids_ref, ids_smem, rows_vmem, fw_vmem,
         sem_ids, sems, sems_f) = rest
    else:
        (vals_ref, ids_ref, ids_smem, rows_vmem, sem_ids, sems) = rest
        filt_hbm = fw_vmem = sems_f = None
    i = pl.program_id(0)
    jc = pl.program_id(1)
    bq, bc = cand_ref.shape
    total = bq * bc

    @pl.when(jc == 0)
    def _init():
        vals_ref[:] = jnp.full_like(vals_ref, jnp.inf)
        ids_ref[:] = jnp.full_like(ids_ref, -1)

    # 1. candidate ids HBM→SMEM (start/wait paired inline — GL08)
    cp = pltpu.make_async_copy(
        cand_hbm.at[pl.ds(i * bq, bq), pl.ds(jc * bc, bc)],
        ids_smem, sem_ids)
    cp.start()
    cp.wait()

    # 2. candidate rows HBM→VMEM, NBUF in flight. The wait recomputes
    # the identical copy descriptor (the documented double-buffer
    # idiom); a slot is always waited before its next start so two
    # copies never share a live semaphore — the graftlint GL08 lifetime
    # contract (the linter verifies the factory's starts all have
    # waits; the t/t+NBUF slot rotation below is the hand-managed part
    # it cannot prove, hence this invariant comment).
    def row_copy(t):
        qq = t // bc
        rr = jax.lax.rem(t, bc)
        row = jnp.clip(ids_smem[qq, rr], 0, n_rows - 1)
        return pltpu.make_async_copy(
            data_hbm.at[pl.ds(row, 1), :],
            rows_vmem.at[pl.ds(t, 1), :],
            sems.at[jax.lax.rem(t, _GATHER_NBUF)])

    def word_copy(t):
        # the candidate's bitset word, addressed off the same SMEM id
        # the row DMA reads (word index = row // 32 — int32-exact: the
        # kernel's ids are int32 by construction, core/ids policy)
        qq = t // bc
        rr = jax.lax.rem(t, bc)
        row = jnp.clip(ids_smem[qq, rr], 0, n_rows - 1)
        w = jnp.minimum(row // 32, filt_hbm.shape[0] - 1)
        return pltpu.make_async_copy(
            filt_hbm.at[pl.ds(w, 1), :],
            fw_vmem.at[pl.ds(qq, 1), pl.ds(rr, 1)],
            sems_f.at[jax.lax.rem(t, _GATHER_NBUF)])

    for t in range(_GATHER_NBUF):  # static warm-up fills the queue
        row_copy(t).start()
        if filtered:
            word_copy(t).start()

    def stream(t, carry):
        row_copy(t).wait()
        if filtered:
            word_copy(t).wait()

        @pl.when(t + _GATHER_NBUF < total)
        def _():
            row_copy(t + _GATHER_NBUF).start()
            if filtered:
                word_copy(t + _GATHER_NBUF).start()

        return carry

    jax.lax.fori_loop(0, total, stream, 0)

    # 3. exact epilogue + running top-k merge
    r3 = rows_vmem[:].astype(jnp.float32).reshape(bq, bc, -1)
    q = q_ref[:]                                       # [bq, dpad] f32
    s = jnp.sum(q[:, None, :] * r3, axis=-1)           # [bq, bc]
    if metric == "ip":
        key = -s
    else:
        rsq = jnp.sum(r3 * r3, axis=-1)                # [bq, bc]
        qsq = jnp.sum(q * q, axis=1)                   # [bq]
        if metric == "cos":
            # mirror _refine_rows' formula exactly (parity over speed:
            # rsqrt would drift ~1e-3 relative on near-duplicate rows)
            qn = jnp.sqrt(jnp.maximum(qsq, 1e-30))
            cn = jnp.sqrt(jnp.maximum(rsq, 1e-30))
            key = 1.0 - s / (qn[:, None] * cn)
        else:  # l2 (sqrt applied by the caller: selection order is equal)
            key = jnp.maximum(qsq[:, None] + rsq - 2.0 * s, 0.0)
    cand = cand_ref[:]                                 # [bq, bc] i32
    valid = cand >= 0
    if filtered:
        # poison masked rows in the metric epilogue: the candidate's
        # keep bit, tested against the word its DMA fetched — the same
        # ±inf/-1 sentinel path invalid ids take (GL13)
        bit = jax.lax.shift_right_logical(fw_vmem[:], cand & 31) & 1
        valid = jnp.logical_and(valid, bit > 0)
    key = jnp.where(valid, key, jnp.inf)
    gid = jnp.where(valid, cand, -1)

    kpad = vals_ref.shape[1]
    comb_v = jnp.concatenate([vals_ref[:], key], axis=1)
    comb_i = jnp.concatenate([ids_ref[:], gid], axis=1)
    out_v, out_i = _extract_topk_block(comb_v, comb_i, k, kpad)
    vals_ref[:] = out_v
    ids_ref[:] = out_i


@functools.partial(jax.jit, static_argnames=("k", "metric", "interpret"))
@annotate(REFINE_SCOPE)
def gather_refine_topk(dataset: jax.Array, queries: jax.Array,
                       candidates: jax.Array, k: int, metric: str = "l2",
                       filter_bits=None,
                       interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array]:
    """Fused exact re-rank of per-query candidate ids — the streaming
    refine half of the oversampled IVF-PQ pipeline (reference: the
    device refine kernel, detail/refine_device.cuh).

    The XLA refine path (`refine.py:_refine_impl`) gathers candidates
    into a materialized ``[m, C, d]`` f32 HBM buffer before one batched
    einsum — at batch 10000 × k_cand 2000 × d 96 that is ~7.7 GB, the
    same accumulator-OOM shape the Pallas LUT scan eliminated on the
    scan side. This kernel instead streams each query tile's candidate
    ids HBM→SMEM and the corresponding ``dataset`` rows HBM→VMEM
    row-by-row (``_GATHER_NBUF`` copies in flight), computes the exact
    distance epilogue in VMEM and keeps a running top-k per query —
    nothing but the ``[m, kpad]`` result tables ever reaches HBM.

    ``dataset [n, d]`` — f32 rows or the bf16 reconstruction cache
    (dtype is preserved through the row DMAs; distances compute in
    f32); ``queries [m, d]``; ``candidates [m, C]`` i32 row ids, -1
    invalid (out-of-range ids are clamped for the DMA and masked only
    if negative, matching the XLA path's clip semantics). A dataset
    whose minor dim is not lane-aligned pays a PER-CALL padded
    ``[n, ceil(d/128)·128]`` HBM copy here (the row DMAs address
    lane-tiled rows) — dispatchers weigh it against the gather buffer
    via ``ivf_common.gather_refine_mem_ok``.

    ``filter_bits``: optional packed uint32 bitset over dataset rows
    (``core.bitset`` layout) — each candidate's word is fetched by the
    row-DMA queue and cleared bits are poisoned to +inf/-1 in the
    epilogue (the streamed filter half of the filtered oversampled
    pipeline).

    Returns (keys [m, k], ids [m, k]): minimized sort keys, sorted
    ascending (l2: squared distance — callers apply sqrt; ip: negated
    score; cos: cosine distance) and global candidate ids (-1 when a
    slot saw fewer than k valid candidates).
    """
    m, d = queries.shape
    n = dataset.shape[0]
    assert metric in ("l2", "ip", "cos")
    if k > GATHER_REFINE_MAX_K:
        raise ValueError(
            f"k={k} > {GATHER_REFINE_MAX_K} (the in-kernel merge is k "
            "extraction rounds per tile — gate with "
            "pallas_gather_refine_wanted)")
    filtered = filter_bits is not None
    bq, bc = GATHER_REFINE_BQ, GATHER_REFINE_BC
    kpad = _LANES
    qf = _pad_to(queries.astype(jnp.float32), bq, 0, 0.0)
    qf = _pad_to(qf, _LANES, 1, 0.0)
    data = _pad_to(dataset, _LANES, 1, 0.0)  # dtype preserved (f32/bf16)
    cand = _pad_to(candidates.astype(jnp.int32), bq, 0, -1)
    cand = _pad_to(cand, bc, 1, -1)
    mp, Cp = cand.shape
    dpad = data.shape[1]

    in_specs = [
        pl.BlockSpec((bq, dpad), lambda i, j: (i, 0)),
        # candidates ride twice: a VMEM block for the validity mask,
        # and the full array in HBM for the in-kernel id DMA (DMA
        # row addresses must come from scalar memory)
        pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [qf, cand, cand, data]
    scratch = [
        pltpu.SMEM((bq, bc), jnp.int32),
        pltpu.VMEM((bq * bc, dpad), data.dtype),
    ]
    if filtered:
        # [W, 1] i32 view of the packed words: per-candidate [1, 1]
        # word DMAs address rows of a 2-D array
        fw = jax.lax.bitcast_convert_type(
            filter_bits, jnp.int32).reshape(-1, 1)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(fw)
        scratch.append(pltpu.VMEM((bq, bc), jnp.int32))
    scratch += [
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA((_GATHER_NBUF,)),
    ]
    if filtered:
        scratch.append(pltpu.SemaphoreType.DMA((_GATHER_NBUF,)))

    grid = (mp // bq, Cp // bc)
    vals, ids = pl.pallas_call(
        functools.partial(_gather_refine_kernel, k=k, metric=metric,
                          n_rows=n, filtered=filtered),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, kpad), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, kpad), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, kpad), jnp.float32),
            jax.ShapeDtypeStruct((mp, kpad), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return vals[:m, :k], ids[:m, :k]


def pallas_gather_refine_wanted(m: int, C: int, d: int, k: int,
                                itemsize: int = 4,
                                filtered: bool = False) -> bool:
    """Dispatch for :func:`gather_refine_topk` — the fused refine tier.

    Needs k within the merge budget and a VMEM-sized gathered-row
    block; auto mode engages on TPU for the oversampled shapes whose
    ``[m, C, d]`` gather buffer is HBM-hostile (k_cand ≥ 400, the
    DEEP-100M refinement_rate regime, or a gather buffer past 1 GB) —
    the XLA einsum path keeps small candidate sets. Env override
    ``RAFT_TPU_PALLAS_REFINE`` = always | never | auto (tri-state, see
    :func:`raft_tpu.obs.env_tristate`) — "on"/"always" runs interpreted
    off-TPU (tests)."""
    force = _env_tristate("RAFT_TPU_PALLAS_REFINE")
    if force == "off" or k > GATHER_REFINE_MAX_K or C < 2 * _LANES:
        return False
    dpad = -(-d // _LANES) * _LANES
    bq, bc = GATHER_REFINE_BQ, GATHER_REFINE_BC
    vmem = (bq * bc * dpad * itemsize     # gathered rows scratch
            + 2 * bq * dpad * 4           # query block (+double buffer)
            + 2 * bq * bc * 4             # candidate id block
            + bq * bc * dpad * 4          # f32 row/broadcast transients
            + 4 * bq * _LANES * 8         # running buffers + extraction
            + (bq * bc * 4 if filtered else 0))  # per-candidate words
    if vmem > _GROUPED_VMEM_BUDGET:
        return False
    if force == "on":
        return True
    return _on_tpu() and (C >= 400 or m * C * d * itemsize >= (1 << 30))


@functools.partial(jax.jit,
                   static_argnames=("k", "select_min", "bm", "bl", "interpret"))
def select_k_pallas(scores: jax.Array, k: int, select_min: bool = True,
                    bm: int = 64, bl: int = 2048,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Batched top-k over rows of ``scores`` [m, len] — Pallas counterpart
    of ``matrix::select_k`` (matrix/select_k.cuh:81).  Returns sorted
    (values [m, k], indices [m, k])."""
    m, n = scores.shape
    if k > n:
        raise ValueError(f"k={k} > len={n}")
    kpad = max(_LANES, ((k + _LANES - 1) // _LANES) * _LANES)
    s = _pad_to(scores.astype(jnp.float32), bm, 0, 0.0)
    s = _pad_to(s, bl, 1, jnp.inf if select_min else -jnp.inf)
    mp, npad = s.shape
    nvalid = jnp.full((1,), n, jnp.int32)

    grid = (mp // bm, npad // bl)
    vals, idx = pl.pallas_call(
        functools.partial(_select_k_kernel, k=k, select_min=select_min),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bl), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, kpad), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, kpad), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, kpad), jnp.float32),
            jax.ShapeDtypeStruct((mp, kpad), jnp.int32),
        ],
        interpret=interpret,
    )(s, nvalid)
    return vals[:m, :k], idx[:m, :k]


# ---------------------------------------------------------------------------
# strided-bin cut: the two best of each of 128 strided bins of a wide row
# ---------------------------------------------------------------------------

# Bytes of one input block: double-buffered, plus the output blocks and
# the running state, it stays inside the default scoped VMEM.
_BIN_BLOCK_BYTES = 4 * 1024 * 1024
_BIN_MAX_COLS = 2048
_BIN_MAX_ROWS = 512


def _bin_blocks(m: int, it: int) -> Tuple[int, int]:
    """(row block, column block) of :func:`bin_two_best` over [m, it],
    m ≥ 8 and it a lane multiple. The column block divides the row; the
    row block is a multiple of 8 that divides m where such a divisor is
    at least half the row cap, else the last row block is ragged."""
    chunks = it // _LANES
    tc = _LANES * max(c for c in range(1, _BIN_MAX_COLS // _LANES + 1)
                      if chunks % c == 0)
    cap = min(_BIN_MAX_ROWS, _BIN_BLOCK_BYTES // (4 * tc), m)
    cap -= cap % _SUBLANES
    tm = next((t for t in range(cap, cap // 2 - 1, -_SUBLANES)
               if m % t == 0), cap)
    return tm, tc


def _bin_two_best_kernel(x_ref, vals_ref, pos_ref, b1_ref, p1_ref, b2_ref,
                         p2_ref, *, select_min: bool, rows: int):
    """Grid = (row blocks, column blocks); columns are the sequential
    minor axis. Bin = column mod 128 is the lane, so the chunk ``t`` of
    128 columns offers one candidate to every bin of a row, and the cut
    is an elementwise running (best, second) per row and lane, kept in
    scratch across column blocks. Strict comparisons over increasing
    ``t`` keep the first of equal values as the best and a later equal
    one as the second; a bin with nothing better than the initial
    value keeps position 0 — XLA's argmin rules, so the result matches
    the two min/argmin passes it replaces bit for bit. Rows go through
    in groups of ``rows`` so the running state stays in registers while
    the block's chunks stream past it."""
    c = pl.program_id(1)
    tm, tc = x_ref.shape
    n_chunks = tc // _LANES
    worst = jnp.inf if select_min else -jnp.inf
    better = jnp.less if select_min else jnp.greater

    @pl.when(c == 0)
    def _init():
        b1_ref[...] = jnp.full(b1_ref.shape, worst, jnp.float32)
        b2_ref[...] = jnp.full(b2_ref.shape, worst, jnp.float32)
        p1_ref[...] = jnp.zeros(p1_ref.shape, jnp.int32)
        p2_ref[...] = jnp.zeros(p2_ref.shape, jnp.int32)

    def group(g, carry):
        rs = pl.ds(pl.multiple_of(g * rows, rows), rows)
        b1, p1 = b1_ref[rs, :], p1_ref[rs, :]
        b2, p2 = b2_ref[rs, :], p2_ref[rs, :]
        for j in range(n_chunks):
            v = x_ref[rs, pl.ds(j * _LANES, _LANES)]
            t = c * n_chunks + j
            first = better(v, b1)
            second = better(v, b2)
            b2 = jnp.where(first, b1, jnp.where(second, v, b2))
            p2 = jnp.where(first, p1, jnp.where(second, t, p2))
            b1 = jnp.where(first, v, b1)
            p1 = jnp.where(first, t, p1)
        b1_ref[rs, :], p1_ref[rs, :] = b1, p1
        b2_ref[rs, :], p2_ref[rs, :] = b2, p2
        return carry

    jax.lax.fori_loop(0, tm // rows, group, 0)

    @pl.when(c == pl.num_programs(1) - 1)
    def _out():
        lane = jax.lax.broadcasted_iota(jnp.int32, (tm, _LANES), 1)
        for half, (b_ref, p_ref) in enumerate(((b1_ref, p1_ref),
                                               (b2_ref, p2_ref))):
            cols = pl.ds(half * _LANES, _LANES)
            b = b_ref[...]
            # a largest-first cut reports every infinite value as -inf
            vals_ref[:, cols] = b if select_min else jnp.where(
                jnp.isinf(b), -jnp.inf, b)
            pos_ref[:, cols] = p_ref[...] * _LANES + lane


@functools.partial(jax.jit, static_argnames=("select_min", "interpret"))
def bin_two_best(dists: jax.Array, select_min: bool = True,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The two best entries of each of 128 STRIDED bins (bin = column
    mod 128) of every row of ``dists`` [m, it], it a multiple of 128,
    with their column positions: ([m, 256] values, [m, 256] positions),
    the best of each bin in the first half and the second best in the
    second. One pass that streams the block from HBM in its native
    layout, where the bin is the lane: no layout copy of the block and
    no second reduction over it. Matches the XLA two-pass cut
    (``neighbors.brute_force._two_best_per_bin`` off the TPU) bit for
    bit, ties and infinite bins included."""
    m, it = dists.shape
    if it % _LANES:
        raise ValueError(f"row length {it} is not a multiple of {_LANES}")
    x = _pad_to(dists, _SUBLANES, 0, 0.0) if m < _SUBLANES else dists
    tm, tc = _bin_blocks(x.shape[0], it)
    rows = next(r for r in (32, 16, 8) if tm % r == 0)
    state = [pltpu.VMEM((tm, _LANES), dt)
             for dt in (jnp.float32, jnp.int32, jnp.float32, jnp.int32)]
    vals, pos = pl.pallas_call(
        functools.partial(_bin_two_best_kernel, select_min=select_min,
                          rows=rows),
        grid=(pl.cdiv(x.shape[0], tm), it // tc),
        in_specs=[pl.BlockSpec((tm, tc), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tm, 2 * _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((tm, 2 * _LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], 2 * _LANES), jnp.float32),
            jax.ShapeDtypeStruct((x.shape[0], 2 * _LANES), jnp.int32),
        ],
        scratch_shapes=state,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x.astype(jnp.float32))
    return vals[:m], pos[:m]


# ---------------------------------------------------------------------------
# ring top-k exchange: reduce-scatter-of-top-k across a mesh axis — each
# device streams only its surviving [mc, k] block to its ring neighbor via
# async remote DMA; the [n_dev, m, k] allgather buffer never exists
# ---------------------------------------------------------------------------

# In-kernel merge budget (k extraction rounds per hop — the same bound the
# gather-refine merge carries).
RING_TOPK_MAX_K = 64
# VMEM working set: recv slots (double-buffered) + running/local blocks
# for vals+ids, all [mc, 128] lane tiles.
_RING_VMEM_BUDGET = 12 * 1024 * 1024


def ring_chunk_rows(m: int, n_dev: int) -> int:
    """Query rows per ring chunk: ceil(m / n_dev) padded to a sublane
    tile. Shared by the kernel, the ppermute fallback, and the comms
    byte accounting so all three agree on the per-hop block shape."""
    mc = -(-m // n_dev)
    return max(_SUBLANES, -(-mc // _SUBLANES) * _SUBLANES)


def ring_topk_kernel_ok(m: int, k: int, n_dev: int) -> bool:
    """Kernel-tier eligibility: merge budget (k extraction rounds per
    hop) and the VMEM working set of the double-buffered exchange.
    Multi-axis meshes are the caller's problem — the kernel addresses
    ring neighbors by LOGICAL device id, so the exchange axis must be
    the whole mesh (the ppermute fallback serves sub-axis rings)."""
    if k > RING_TOPK_MAX_K or n_dev < 2:
        return False
    mc = ring_chunk_rows(m, n_dev)
    vmem = (2 * mc * _LANES * 8      # recv slots (vals+ids, double buffer)
            + 2 * mc * _LANES * 8    # running + local staging blocks
            + 2 * mc * 3 * _LANES * 8)  # extraction transients
    return vmem <= _RING_VMEM_BUDGET


def ring_topk_inner_ok(m: int, k: int, n_inner: int) -> bool:
    """Eligibility of the ring kernel as the hier tier's per-pod
    (inner-axis) stage. Same merge/VMEM budget as
    :func:`ring_topk_kernel_ok`, but the exchange axis is a SUB-axis:
    the kernel's neighbor addressing is by logical device id, so the
    per-pod ring passes ``outer_axis`` to :func:`ring_topk_merge` and
    offsets neighbors by the pod base — which is only the right flat id
    when the inner axis is the MINOR (trailing) mesh axis, the layout
    :func:`raft_tpu.parallel.mesh.hier_mesh` guarantees (logical id =
    dcn_idx·n_inner + ici_idx). Callers on other layouts must use the
    ppermute fallback."""
    return ring_topk_kernel_ok(m, k, n_inner)


def ring_topk_splits(mc: int, schedule: str) -> Tuple[Tuple[int, int], ...]:
    """Row sub-blocks of one [mc, kpad] hop block, as (offset, rows)
    pairs. The ``serial`` schedule is one block — the PR-8 bulk-
    synchronous ring. The ``overlap`` schedule splits the block into two
    sublane-aligned halves so each half's hop-(s+1) transfer can start
    as soon as ITS merge lands, while the other half of hop s is still
    being merged — the compute/comms overlap. Chunks too short to split
    (mc < 16) degenerate to one block either way; the byte model is
    untouched (same rows cross the link per hop, in 2 DMAs instead
    of 1)."""
    if schedule == "serial" or mc < 2 * _SUBLANES:
        return ((0, mc),)
    mh = (mc // 2 // _SUBLANES) * _SUBLANES
    return ((0, mh), (mh, mc - mh))


def _ring_topk_kernel(vals_hbm, ids_hbm, out_v_ref, out_i_ref,
                      buf_v, buf_i, run_v, run_i, loc_v, loc_i,
                      send_sems, recv_sems, cap_sems, copy_sems, *,
                      k: int, n_dev: int, mc: int, axis_name: str,
                      flow_control: bool, splits,
                      outer_axis: Optional[str] = None):
    """One device's program of the ring reduce-scatter-of-top-k.

    The local [n_dev·mc, kpad] candidate table lives in HBM; chunk ``c``
    (rows [c·mc, (c+1)·mc)) is query chunk ``c``'s local top-k. Chunk
    ``c``'s partial starts at device ``(c+1) mod n_dev`` and travels the
    ring for ``n_dev−1`` hops, merged against each host device's local
    chunk on the way, landing fully merged at its owner ``c``.

    The hop block is cut into ``splits`` row sub-blocks (see
    :func:`ring_topk_splits`) and the schedule is software-pipelined
    across the hop boundary, per sub-block ``h``:

    1. hop s's transfers for ``h`` were started at the END of hop s−1
       (prologue for hop 0), so they are in flight while hop s−1's
       later sub-blocks are still being merged — with two halves, hop
       s's exchange rides under hop s−1's on-chip merge work and vice
       versa. The owning chunk's local HBM→VMEM copies start in the
       same breath and hide under the same transfer.
    2. recv slots are double-buffered (slot = s mod 2) per sub-block,
       so the LEFT neighbor — which may run a hop ahead — can land hop
       s+1's half in slot (s+1)%2 while this device still merges slot
       s%2;
    3. waits gate only slot reuse: the send wait (running sub-block
       about to be overwritten by ITS merge), the recv wait (this
       half's incoming partial landed — SPMD symmetry), and the local
       copy wait. Then the k-round extraction merge
       (``_extract_topk_block``) reduces incoming ++ local to the
       surviving top-k, and the NEXT hop's send/recv pair for this
       half starts immediately — before the next half's merge runs.

    ``flow_control``: on real hardware a capacity semaphore per
    (slot, half) guards slot reuse (the right neighbor confirms it
    consumed (s, h) before the step-s+2 send restarts that slot) and a
    neighbor barrier aligns kernel entry; interpret mode executes
    remote copies synchronously and implements neither remote signal,
    so both are compiled out there.
    """
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, n_dev)
    left = jax.lax.rem(my + n_dev - 1, n_dev)
    if outer_axis is not None:
        # per-pod ring on a (outer, inner) mesh: neighbor semaphores and
        # DMAs address LOGICAL (flat) device ids, and axis_index(inner)
        # is only pod-relative — offset by this pod's base so the ring
        # stays inside the pod (requires inner = minor mesh axis, see
        # ring_topk_inner_ok)
        base = jax.lax.axis_index(outer_axis) * n_dev
        right = base + right
        left = base + left
    H = len(splits)

    if flow_control:
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

    def chunk_copy(hbm, dst, c, h, which):
        off, rows = splits[h]
        return pltpu.make_async_copy(
            hbm.at[pl.ds(c * mc + off, rows)],
            dst.at[pl.ds(off, rows)], copy_sems.at[h, which])

    def ring_send(slot, h, which):
        off, rows = splits[h]
        src = run_v if which == 0 else run_i
        dst = buf_v if which == 0 else buf_i
        return pltpu.make_async_remote_copy(
            src_ref=src.at[pl.ds(off, rows)],
            dst_ref=dst.at[slot, pl.ds(off, rows)],
            send_sem=send_sems.at[slot, h, which],
            recv_sem=recv_sems.at[slot, h, which],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def hop_chunk(s):
        # the partial arriving at hop s is chunk (my − s − 2)'s
        return jax.lax.rem(my + 2 * n_dev - s - 2, n_dev)

    # init: this device starts chunk (my−1)'s journey with its local block
    c0 = jax.lax.rem(my + n_dev - 1, n_dev)
    for h in range(H):
        chunk_copy(vals_hbm, run_v, c0, h, 0).start()
        chunk_copy(ids_hbm, run_i, c0, h, 1).start()
    for h in range(H):
        chunk_copy(vals_hbm, run_v, c0, h, 0).wait()
        chunk_copy(ids_hbm, run_i, c0, h, 1).wait()

    # prologue: hop 0's sends + local-chunk copies, all sub-blocks
    for h in range(H):
        ring_send(0, h, 0).start()
        ring_send(0, h, 1).start()
        chunk_copy(vals_hbm, loc_v, hop_chunk(0), h, 0).start()
        chunk_copy(ids_hbm, loc_i, hop_chunk(0), h, 1).start()

    for s in range(n_dev - 1):  # static unroll: n_dev−1 hops
        slot = s % 2
        nxt = (s + 1) % 2
        c = hop_chunk(s)
        for h in range(H):
            off, rows = splits[h]
            # waits gate slot reuse only: the send (its merge overwrites
            # run), the recv (this half's partial landed), the local copy
            ring_send(slot, h, 0).wait()
            ring_send(slot, h, 1).wait()
            chunk_copy(vals_hbm, loc_v, c, h, 0).wait()
            chunk_copy(ids_hbm, loc_i, c, h, 1).wait()
            comb_v = jnp.concatenate(
                [buf_v[slot, pl.ds(off, rows)], loc_v[pl.ds(off, rows)]],
                axis=1)
            comb_i = jnp.concatenate(
                [buf_i[slot, pl.ds(off, rows)], loc_i[pl.ds(off, rows)]],
                axis=1)
            mv, mi = _extract_topk_block(comb_v, comb_i, k,
                                         run_v.shape[1])
            run_v[pl.ds(off, rows)] = mv
            run_i[pl.ds(off, rows)] = mi
            if flow_control and s + 2 <= n_dev - 2:
                # this half's recv slot is consumed: free it for the
                # left neighbor's step-s+2 send
                pltpu.semaphore_signal(
                    cap_sems.at[slot, h], inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            if s + 1 <= n_dev - 2:
                # start the NEXT hop's pair for this half NOW — before
                # the next half's merge — so hop s+1's transfer rides
                # under the remaining hop-s merge work
                if flow_control and s + 1 >= 2:
                    # right neighbor consumed (s−1, h) → slot reusable
                    pltpu.semaphore_wait(cap_sems.at[nxt, h], 1)
                ring_send(nxt, h, 0).start()
                ring_send(nxt, h, 1).start()
                chunk_copy(vals_hbm, loc_v, hop_chunk(s + 1), h,
                           0).start()
                chunk_copy(ids_hbm, loc_i, hop_chunk(s + 1), h,
                           1).start()
    out_v_ref[:] = run_v[:]
    out_i_ref[:] = run_i[:]


def ring_schedule(schedule: str = "auto") -> str:
    """Resolve the ring kernel's hop schedule: ``overlap`` (default —
    half-pipelined, hop i's merge runs under hop i+1's in-flight remote
    copy) or ``serial`` (the PR-8 bulk-synchronous schedule, kept for
    the bench comparison column). ``RAFT_TPU_RING_OVERLAP`` = auto | on
    | off (tri-state, :func:`raft_tpu.obs.env_tristate`) decides
    ``auto``; an explicit argument wins."""
    if schedule in ("overlap", "serial"):
        return schedule
    force = _env_tristate("RAFT_TPU_RING_OVERLAP")
    return "serial" if force == "off" else "overlap"


def ring_topk_merge(vals: jax.Array, ids: jax.Array, k: int,
                    axis_name: str, n_dev: int, select_min: bool = True,
                    interpret: bool = False, schedule: str = "auto",
                    outer_axis: Optional[str] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Ring reduce-scatter-of-top-k over a mesh axis — the Pallas merge
    tier replacing allgather-and-select (reference: knn_merge_parts.cuh
    merged over NCCL in raft-dask; here the merge IS the transport).

    Must be called inside ``shard_map`` over ``axis_name`` (a 1-D mesh:
    neighbors are addressed by logical device id — see
    :func:`ring_topk_kernel_ok`). On a 2-D (outer, inner) hier mesh pass
    ``outer_axis`` so the per-pod ring offsets its neighbor ids by the
    pod base (inner must be the minor mesh axis —
    :func:`ring_topk_inner_ok`). ``vals``/``ids`` [m, k'] (k' ≥ k) are
    this device's local top-k table, ids -1 invalid, invalid keys at the
    select sentinel (+inf for ``select_min``, −inf otherwise). Returns
    this device's owned query chunk ([mc, k] — rows
    [rank·mc, (rank+1)·mc) of the padded query axis): callers emit
    ``P(axis)`` out_specs and slice the assembled [n_dev·mc, k] back to
    [m, k]. The allgather buffer is gone: per hop only the surviving
    [mc, k] block crosses the interconnect, counted per hop as
    ``comms.ops/bytes{op=ring_topk}`` by the dispatching merge tier.

    ``schedule`` = auto | overlap | serial (:func:`ring_schedule`):
    both are exact-parity, the overlap schedule pipelines each hop's
    merge under the next hop's in-flight exchange.
    """
    m, kin = vals.shape
    if k > kin:
        raise ValueError(f"k={k} > candidate width {kin}")
    if k > RING_TOPK_MAX_K:
        raise ValueError(
            f"k={k} > {RING_TOPK_MAX_K} (the in-kernel merge is k "
            "extraction rounds per hop — gate with ring_topk_kernel_ok)")
    mc = ring_chunk_rows(m, n_dev)
    splits = ring_topk_splits(mc, ring_schedule(schedule))
    H = len(splits)
    m_pad = mc * n_dev
    kpad = _LANES
    keys = vals.astype(jnp.float32)
    if not select_min:
        keys = -keys  # uniform ascending selection; −inf pads → +inf
    keys = _pad_to(keys, m_pad, 0, jnp.inf) if m_pad > m else keys
    keys = _pad_to(keys, kpad, 1, jnp.inf)
    idp = ids.astype(jnp.int32)
    idp = _pad_to(idp, m_pad, 0, -1) if m_pad > m else idp
    idp = _pad_to(idp, kpad, 1, -1)
    # invalid slots must carry the internal sentinel regardless of the
    # caller's pad value convention
    keys = jnp.where(idp < 0, jnp.inf, keys)

    kwargs = {}
    if not interpret:
        # the neighbor barrier needs a collective id (real hardware only)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            collective_id=1)
    out_v, out_i = pl.pallas_call(
        functools.partial(_ring_topk_kernel, k=k, n_dev=n_dev, mc=mc,
                          axis_name=axis_name,
                          flow_control=not interpret, splits=splits,
                          outer_axis=outer_axis),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((mc, kpad), lambda: (0, 0)),
            pl.BlockSpec((mc, kpad), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mc, kpad), jnp.float32),
            jax.ShapeDtypeStruct((mc, kpad), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, mc, kpad), jnp.float32),   # recv slots (vals)
            pltpu.VMEM((2, mc, kpad), jnp.int32),     # recv slots (ids)
            pltpu.VMEM((mc, kpad), jnp.float32),      # running block
            pltpu.VMEM((mc, kpad), jnp.int32),
            pltpu.VMEM((mc, kpad), jnp.float32),      # local chunk staging
            pltpu.VMEM((mc, kpad), jnp.int32),
            pltpu.SemaphoreType.DMA((2, H, 2)),       # send: slot×half×array
            pltpu.SemaphoreType.DMA((2, H, 2)),       # recv
            pltpu.SemaphoreType.REGULAR((2, H)),      # slot capacity
            pltpu.SemaphoreType.DMA((H, 2)),          # local chunk copies
        ],
        interpret=interpret,
        **kwargs,
    )(keys, idp)
    res_v = out_v[:, :k]
    res_i = out_i[:, :k]
    if not select_min:
        res_v = jnp.where(jnp.isinf(res_v), -jnp.inf, -res_v)
    return res_v, res_i


# ---------------------------------------------------------------------------
# fused scan-in-ring: the per-shard LUT scan folded INTO the ring schedule —
# chunk c_s's list scan hides under hop s's in-flight exchange, and the
# per-shard [m, k] candidate table handed from the scan stage to the merge
# never materializes in HBM
# ---------------------------------------------------------------------------

# Additive key bias marking un-probed (query, list) pairs in the fused
# scan-in-ring kernel. A finite sentinel rather than +inf: the bias rides
# through an f32 selection matmul (inf·0 = NaN) and real ADC keys are
# bounded by the data scale (≪ 1e29), so biased keys are thresholded back
# to the +inf/-1 empty-slot convention at segment extraction.
_LUT_MASK_BIG = 1e30
# Union-probe segments per ring chunk the fused kernel will serve: the
# scan loop is NS·n_t tiles per chunk, and the [n_dev, NS] list table
# must fit SMEM.
RING_FUSED_MAX_SEGS = 512


def _ring_lut_scan_kernel(cl_smem, ind_hbm, qv_hbm, codes_hbm, ids_hbm,
                          norms_hbm, ctr_hbm, *rest,
                          k: int, n_dev: int, mc: int, NS: int, n_t: int,
                          metric: str, pq_bits: int, S: int, P: int,
                          G: int, Sg: int, Kc: int, L: int, Rt: int,
                          rot: int, rotp: int, indl: int,
                          axis_name: str, flow_control: bool,
                          filtered: bool):
    """One device's program of the fused scan-in-ring search.

    The ring schedule is the serialized PR-8 exchange; what fills the
    dead time is the SCAN. Per ring step the device must merge the
    incoming partial against its local top-k of query chunk ``c`` — and
    in this kernel that local top-k does not pre-exist in HBM: it is
    computed ON THE SPOT, between the send start and the recv wait, by
    streaming the chunk's union probe lists' packed codes through the
    shared LUT-scan tile body (:func:`_lut_tile_update`). The chunk's
    candidates live only in the ``cand_*`` VMEM blocks; the per-shard
    ``[m, k]`` table the unfused pipeline hands from ``search`` to
    ``merge_topk`` never exists.

    Chunk scan: per segment p (one union list, −1 pads clamped and
    masked), the list's code tiles stream HBM→VMEM double-buffered
    (slots alternate per tile, each waited before reuse — GL08);
    per-query probe membership rides an additive ``_LUT_MASK_BIG`` key
    bias (the [1, mc] indicator row is transposed to a [mc, 1] column
    by an exact iota-eye matmul — Mosaic has no sublane gather), so a
    chunk query that did not probe the list contributes nothing after
    the segment extraction thresholds biased keys back to +inf/-1.
    Per-segment 2-deep strided bins (reset at tile 0, extracted at the
    last tile) keep candidate semantics identical to the standalone
    ``ivfpq_lut_scan_topk`` tier: per (query, probed list), the two
    best per strided bin, then a running k-merge across lists.

    Ring: identical slot/semaphore discipline to
    :func:`_ring_topk_kernel`'s serial schedule (double-buffered recv
    slots, capacity semaphores + entry barrier compiled out in
    interpret mode) — the overlap here comes from the scan, not from
    half-splitting.

    ``filtered`` streams each list's packed per-candidate filter bytes
    through the tile-copy queue (a 4th double-buffered slot beside
    codes/ids/norms), unpacked per tile with the code-unpack shift/mask
    machinery and folded into the shared tile body's sentinel epilogue
    — the per-shard bitset slice composed with the global→local remap
    happens host-side (``parallel.ivf``)."""
    if filtered:
        (fbytes_hbm, sel_lo_ref, sel_hi_ref, off_ref, cbp_ref,
         fsel_ref, foff_ref, out_v_ref, out_i_ref,
         qv_vmem, ctr_vmem, ind_vmem, code_sl, idrow_sl, nrow_sl,
         fb_sl, qc_col, bias_col, b1k, b1i, b2k, b2i, cand_v, cand_i,
         run_v, run_i, buf_v, buf_i, qv_sem, seg_sems, tile_sems,
         send_sems, recv_sems, cap_sems) = rest
    else:
        (sel_lo_ref, sel_hi_ref, off_ref, cbp_ref, out_v_ref, out_i_ref,
         qv_vmem, ctr_vmem, ind_vmem, code_sl, idrow_sl, nrow_sl,
         qc_col, bias_col, b1k, b1i, b2k, b2i, cand_v, cand_i,
         run_v, run_i, buf_v, buf_i, qv_sem, seg_sems, tile_sems,
         send_sems, recv_sems, cap_sems) = rest
        fbytes_hbm = fsel_ref = foff_ref = fb_sl = None
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, n_dev)
    left = jax.lax.rem(my + n_dev - 1, n_dev)
    K = 1 << pq_bits
    kpad = run_v.shape[1]
    T = NS * n_t

    if flow_control:
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

    # sentinel inits anchored on a real operand (see _extract_topk_block:
    # bare paired broadcasted-constant stores abort XLA CPU's sharding
    # propagation in a discharged kernel that also issued a remote DMA)
    def fill_bins(anchor_f, anchor_i):
        cols = jax.lax.broadcasted_iota(jnp.int32, (mc, _LANES), 1)
        b1k[:] = jnp.where(cols < 0, anchor_f[:, :_LANES], jnp.inf)
        b2k[:] = jnp.where(cols < 0, anchor_f[:, :_LANES], jnp.inf)
        b1i[:] = jnp.where(cols < 0, anchor_i[:, :_LANES], -1)
        b2i[:] = jnp.where(cols < 0, anchor_i[:, :_LANES], -1)

    Fbt = G * Rt // 8

    def tile_copies(c, t, sl):
        p = t // n_t
        tt = jax.lax.rem(t, n_t)
        lst = jnp.maximum(cl_smem[c, p], 0)
        copies = (
            pltpu.make_async_copy(
                codes_hbm.at[pl.ds(lst, 1), pl.ds(tt * Rt, Rt), :],
                code_sl.at[pl.ds(sl, 1)], tile_sems.at[sl, 0]),
            pltpu.make_async_copy(
                ids_hbm.at[pl.ds(lst, 1), pl.ds(tt * G * Rt, G * Rt)],
                idrow_sl.at[pl.ds(sl, 1)], tile_sems.at[sl, 1]),
            pltpu.make_async_copy(
                norms_hbm.at[pl.ds(lst, 1), pl.ds(tt * G * Rt, G * Rt)],
                nrow_sl.at[pl.ds(sl, 1)], tile_sems.at[sl, 2]),
        )
        if filtered:
            copies += (pltpu.make_async_copy(
                fbytes_hbm.at[pl.ds(lst, 1), pl.ds(tt * Fbt, Fbt)],
                fb_sl.at[pl.ds(sl, 1)], tile_sems.at[sl, 3]),)
        return copies

    def scan_chunk(c):
        """Stream chunk ``c``'s union probe lists; leaves the chunk's
        local top-k in ``cand_v``/``cand_i``."""
        cp = pltpu.make_async_copy(qv_hbm.at[pl.ds(c, 1)], qv_vmem,
                                   qv_sem)
        cp.start()
        cp.wait()
        qv = qv_vmem[0]                              # [mc, rotp]
        cols_k = jax.lax.broadcasted_iota(jnp.int32, (mc, kpad), 1)
        cand_v[:] = jnp.where(cols_k < 0, qv[:, :kpad], jnp.inf)
        cand_i[:] = jnp.where(cols_k < 0, cols_k, -1)
        for cc in tile_copies(c, 0, 0):
            cc.start()

        def step(t, sl):
            p = t // n_t
            tt = jax.lax.rem(t, n_t)

            @pl.when(tt == 0)
            def _seg_head():
                # per-segment operands: the list's rotated center row +
                # the chunk's probe-indicator row (blocking: once per
                # NS·n_t tiles), and fresh bins
                lst = jnp.maximum(cl_smem[c, p], 0)
                s1 = pltpu.make_async_copy(
                    ctr_hbm.at[pl.ds(lst, 1), :], ctr_vmem,
                    seg_sems.at[0])
                s2 = pltpu.make_async_copy(
                    ind_hbm.at[pl.ds(c, 1), pl.ds(p, 1), :], ind_vmem,
                    seg_sems.at[1])
                s1.start()
                s2.start()
                s1.wait()
                s2.wait()
                # per-segment scalars, staged once for the segment's
                # n_t tiles: ⟨q, c⟩ against the just-landed center row,
                # and the probe-indicator lane row → sublane column via
                # an exact iota-eye matmul (Mosaic has no sublane
                # gather) → the additive _LUT_MASK_BIG key bias
                ctr = ctr_vmem[:]                    # [1, rotp]
                qc_col[:] = jnp.broadcast_to(
                    jnp.sum(qv * ctr, axis=1)[:, None], (mc, _LANES))
                ind = ind_vmem[0]                    # [1, indl]
                eye = (jax.lax.broadcasted_iota(jnp.int32, (mc, indl), 0)
                       == jax.lax.broadcasted_iota(
                           jnp.int32, (mc, indl), 1)).astype(jnp.float32)
                mcol = jax.lax.dot_general(
                    eye, ind, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)  # [mc, 1]
                bias_col[:] = jnp.broadcast_to(
                    (1.0 - mcol) * _LUT_MASK_BIG, (mc, _LANES))
                fill_bins(qv, cols_k)

            for cc in tile_copies(c, t, sl):
                cc.wait()

            @pl.when(t + 1 < T)
            def _prefetch():  # next tile rides under this tile's compute
                for cc in tile_copies(c, t + 1, 1 - sl):
                    cc.start()

            bytes_f = code_sl[sl].astype(jnp.int32).astype(jnp.float32)
            code = _lut_unpack_codes(bytes_f, sel_lo_ref[:],
                                     sel_hi_ref[:], off_ref[:],
                                     pq_bits, K)
            filt_row = None
            if filtered:
                fb_f = fb_sl[pl.ds(sl, 1)].astype(jnp.int32).astype(
                    jnp.float32)
                filt_row = _lut_unpack_filter(fb_f, fsel_ref[:],
                                              foff_ref[:])
            # per-segment scalars staged by _seg_head (computed once
            # per NS·n_t tiles, not per tile)
            qc = qc_col[:, 0]                        # [mc] ⟨q, c⟩
            bias = bias_col[:, :1]                   # [mc, 1]
            state = (b1k[:], b1i[:], b2k[:], b2i[:])
            nb1k, nb1i, nb2k, nb2i = _lut_tile_update(
                code, qv, qc, idrow_sl.at[pl.ds(sl, 1)],
                nrow_sl.at[pl.ds(sl, 1)], cbp_ref, tt, state,
                metric=metric, pq_bits=pq_bits, S=S, P=P, G=G, Sg=Sg,
                Kc=Kc, L=L, Rt=Rt, rot=rot, rotp=rotp,
                exact=cbp_ref.dtype == jnp.float32, key_bias=bias,
                filt_row=filt_row)
            b1k[:] = nb1k
            b1i[:] = nb1i
            b2k[:] = nb2k
            b2i[:] = nb2i

            @pl.when(tt == n_t - 1)
            def _seg_tail():
                # extraction merge: this segment's bins ++ the chunk's
                # running candidates; biased (un-probed) keys threshold
                # back to the +inf/-1 empty-slot convention first
                bins_k = jnp.concatenate([b1k[:], b2k[:]], axis=1)
                bins_i = jnp.concatenate([b1i[:], b2i[:]], axis=1)
                drop = bins_k >= _LUT_MASK_BIG * 0.5
                bins_k2 = jnp.where(drop, jnp.inf, bins_k)
                bins_i2 = jnp.where(drop, -1, bins_i)
                comb_v = jnp.concatenate([cand_v[:], bins_k2], axis=1)
                comb_i = jnp.concatenate([cand_i[:], bins_i2], axis=1)
                mv, mi = _extract_topk_block(comb_v, comb_i, k, kpad)
                cand_v[:] = mv
                cand_i[:] = mi

        def pair_body(j, carry):
            # two tiles per iteration so the double-buffer slots stay
            # STATIC (dynamic leading-index VMEM reads are off the
            # Mosaic fast path); tile indices stay traced
            t0 = 2 * j
            step(t0, 0)

            @pl.when(t0 + 1 < T)
            def _odd():
                step(t0 + 1, 1)

            return carry

        jax.lax.fori_loop(0, (T + 1) // 2, pair_body, 0)

    def ring_send(slot, which):
        src = run_v if which == 0 else run_i
        dst = buf_v if which == 0 else buf_i
        return pltpu.make_async_remote_copy(
            src_ref=src, dst_ref=dst.at[slot],
            send_sem=send_sems.at[slot, which],
            recv_sem=recv_sems.at[slot, which],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    # init: chunk (my−1)'s journey starts with its freshly scanned top-k
    c0 = jax.lax.rem(my + n_dev - 1, n_dev)
    scan_chunk(c0)
    run_v[:] = cand_v[:]
    run_i[:] = cand_i[:]
    for s in range(n_dev - 1):  # static unroll: n_dev−1 hops
        slot = s % 2
        if flow_control and s >= 2:
            pltpu.semaphore_wait(cap_sems.at[slot], 1)
        ring_send(slot, 0).start()
        ring_send(slot, 1).start()
        # the hop's merge partner is chunk (my − s − 2)'s local top-k:
        # SCAN it now, under the in-flight exchange — this is the
        # compute the serialized pipeline ran before the ring started
        c = jax.lax.rem(my + 2 * n_dev - s - 2, n_dev)
        scan_chunk(c)
        ring_send(slot, 0).wait()
        ring_send(slot, 1).wait()
        comb_v = jnp.concatenate([buf_v[slot], cand_v[:]], axis=1)
        comb_i = jnp.concatenate([buf_i[slot], cand_i[:]], axis=1)
        mv, mi = _extract_topk_block(comb_v, comb_i, k, kpad)
        run_v[:] = mv
        run_i[:] = mi
        if flow_control and s + 2 <= n_dev - 2:
            pltpu.semaphore_signal(cap_sems.at[slot], inc=1,
                                   device_id=left,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)
    out_v_ref[:] = run_v[:]
    out_i_ref[:] = run_i[:]


def ring_lut_scan_kernel_ok(S: int, K: int, P: int, nb: int, Wb: int, mc: int,
                     NS: int, k: int, n_dev: int, rot: int,
                     lut_dtype: str = "float32",
                     filtered: bool = False) -> bool:
    """Admission for :func:`ring_lut_scan_merge`: the packed layout must
    be one the in-kernel unpack supports, the merge budget holds (k
    extraction rounds per segment and per hop), the union-segment table
    fits the scan loop, and the VMEM working set — chunk queries + code
    slots + codebook operand + bins + ring blocks (+ the filter-byte
    slots and unpack selection matrix when ``filtered``) — fits the
    budget."""
    if k > RING_TOPK_MAX_K or n_dev < 2 or NS > RING_FUSED_MAX_SEGS:
        return False
    cfg = _lut_scan_config(S, K, P, nb, Wb, lut_dtype)
    if cfg is None:
        return False
    G, Sg, Kc = cfg
    op_bytes = 4 if lut_dtype == "float32" else 2
    rotp = -(-rot // _LANES) * _LANES
    Rt = 2 * _LANES
    vmem_f = _filter_vmem_bytes(G, Rt) if filtered else 0
    vmem = vmem_f + (
        mc * rotp * 4                  # chunk queries
        + 2 * Rt * max(Wb, _LANES)     # u8 code slots (double buffer)
        + 2 * 2 * G * Rt * 8           # id + norm rows (2 slots)
        + Rt * G * S * 8               # unpacked bytes + codes (f32+i32)
        + S * K * P * Sg * op_bytes    # grouped block-diag codebooks
        + _LANES * Kc * Sg * 8         # one-hot transient (+tiled codes)
        + _LANES * rotp * 4            # decoded block
        + mc * _LANES * 4              # qd block
        + mc * indl_pad(mc) * 4        # probe-indicator eye transient
        + 2 * mc * _LANES * 4          # staged per-segment ⟨q,c⟩ + bias
        + 4 * mc * _LANES * 8          # 2-deep bins (keys+ids)
        + 10 * mc * _LANES * 8         # cand/run/recv ring blocks
        + 2 * Wb * G * S * 4           # selection matrices
    )
    return vmem <= _GROUPED_VMEM_BUDGET


def indl_pad(mc: int) -> int:
    """Lane padding of the probe-indicator rows (one lane per chunk
    query row)."""
    return -(-mc // _LANES) * _LANES


def ring_lut_scan_merge(chunk_lists: jax.Array, probe_ind: jax.Array,
                        qv_chunks: jax.Array, packed: jax.Array,
                        ids: jax.Array, norms: jax.Array,
                        centers_rot: jax.Array, codebooks: jax.Array,
                        k: int, metric: str = "l2", *, pq_bits: int,
                        pq_dim: int, L: int, axis_name: str, n_dev: int,
                        lut_dtype: str = "float32",
                        filter_bytes=None,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Fused per-shard LUT scan + ring top-k exchange — codes to merged
    top-k in ONE persistent kernel (ROADMAP item 5's end state for the
    sharded hot path).

    Must be called inside ``shard_map`` over ``axis_name`` on a 1-D
    mesh. The query axis is pre-split into the ring's n_dev chunks:

    - ``chunk_lists [n_dev, NS]`` i32 — each chunk's union of probed
      lists, −1 pad (replicated; lives in SMEM);
    - ``probe_ind [n_dev, NS, mc]`` f32 — 1 where chunk query row r
      probed that list (0 rows make a pad segment inert);
    - ``qv_chunks [n_dev, mc, rot]`` f32 — ROTATED queries per chunk;
    - ``packed`` / ``ids`` / ``norms`` / ``centers_rot`` /
      ``codebooks`` — this shard's index arrays, exactly as
      :func:`ivfpq_lut_scan_topk` takes them (ids must be GLOBAL row
      ids, as the sharded build bakes them).

    Per ring step the kernel scans the next chunk's lists UNDER the
    in-flight exchange and merges on arrival; the per-shard ``[m, k]``
    candidate table never reaches HBM — the only HBM traffic beyond
    the streamed index arrays is the [mc, 128] result block. Keys
    follow the LUT-scan convention (l2: ‖c+d‖² − 2⟨q,c+d⟩, caller adds
    ‖q‖²; ip: −⟨q,c+d⟩); comms bytes are the ring tier's (count via
    ``Comms.count_ring_topk``, byte model unchanged).

    ``filter_bytes`` [n_lists, ceil(L/8)] u8 — optional per-candidate
    packed filter mask over THIS SHARD's list slots
    (``sample_filter.pack_mask_bytes`` of the shard-sliced,
    local-id-remapped keep mask — see ``parallel.ivf``): streamed per
    code tile beside the codes and masked to the sentinel in the shared
    tile body, so filtered pod-scale search rides the ring kernel too.

    Returns (keys [mc, 128], ids [mc, 128]) — this device's owned query
    chunk, ascending, ids −1 for empty slots; callers emit ``P(axis)``
    out-specs and slice ``[:, :k]``.
    """
    n_dev2, mc, rot = qv_chunks.shape
    NS = chunk_lists.shape[1]
    S, K, Pl = codebooks.shape
    assert metric in ("l2", "ip")
    assert S == pq_dim and K == (1 << pq_bits) and n_dev2 == n_dev
    if k > RING_TOPK_MAX_K:
        raise ValueError(
            f"k={k} > {RING_TOPK_MAX_K} (the in-kernel merge is k "
            "extraction rounds per segment/hop — gate with "
            "ring_lut_scan_kernel_ok)")
    nb = (S * pq_bits + 7) // 8
    Wb = packed.shape[2]
    cfg = _lut_scan_config(S, K, Pl, nb, Wb, lut_dtype)
    if cfg is None:
        raise ValueError(
            f"unsupported packed-code layout for the fused scan-in-ring "
            f"kernel: nb={nb} Wb={Wb} (gate with ring_lut_scan_kernel_ok)")
    G, Sg, Kc = cfg

    R = packed.shape[1]
    Rt = 2 * _LANES if R >= 2 * _LANES else _LANES
    n_t = -(-R // Rt)
    # the manual tile DMAs address [tt·Rt, (tt+1)·Rt) directly — pad the
    # stored arrays to whole tiles (the grid pipeline clamps for the
    # standalone kernel; a raw make_async_copy must not read OOB)
    if packed.shape[1] < n_t * Rt:
        packed = _pad_to(packed, n_t * Rt, 1, 0)
    ids = _pad_to(ids, G * n_t * Rt, 1, -1)
    norms = _pad_to(norms, G * n_t * Rt, 1, 0.0)
    filtered = filter_bytes is not None
    Fbt = G * Rt // 8
    if filtered:
        fbits = _pad_to(filter_bytes, n_t * Fbt, 1, 0)
        fsel, foff = _filter_unpack_operands(G * Rt)

    qvp = _pad_to(qv_chunks.astype(jnp.float32), _LANES, 2, 0.0)
    rotp = qvp.shape[2]
    ctr = _pad_to(centers_rot.astype(jnp.float32), _LANES, 1, 0.0)
    indl = indl_pad(mc)
    ind = _pad_to(probe_ind.astype(jnp.float32), indl, 2, 0.0)

    sel_lo, sel_hi, off_arr, cbp = _lut_scan_operands(
        codebooks, pq_bits, nb, Wb, G, Sg, lut_dtype)
    n_sg = S // Sg

    kpad = _LANES
    kwargs = {}
    if not interpret:
        # distinct collective id from ring_topk_merge: a fused search
        # and a plain merge must never share a barrier semaphore
        kwargs["compiler_params"] = pltpu.CompilerParams(
            collective_id=2)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),    # chunk_lists
        pl.BlockSpec(memory_space=pl.ANY),     # probe indicator
        pl.BlockSpec(memory_space=pl.ANY),     # chunk queries
        pl.BlockSpec(memory_space=pl.ANY),     # packed codes
        pl.BlockSpec(memory_space=pl.ANY),     # ids
        pl.BlockSpec(memory_space=pl.ANY),     # norms
        pl.BlockSpec(memory_space=pl.ANY),     # rotated centers
    ]
    operands = [chunk_lists.astype(jnp.int32), ind, qvp, packed, ids,
                norms, ctr]
    if filtered:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # fbytes
        operands.append(fbits)
    in_specs += [
        pl.BlockSpec((Wb, G * S), lambda: (0, 0)),
        pl.BlockSpec((Wb, G * S), lambda: (0, 0)),
        pl.BlockSpec((1, G * S), lambda: (0, 0)),
        pl.BlockSpec((n_sg, K * Sg, Sg * Pl), lambda: (0, 0, 0)),
    ]
    operands += [sel_lo, sel_hi, off_arr, cbp]
    if filtered:
        in_specs += [
            pl.BlockSpec((Fbt, G * Rt), lambda: (0, 0)),
            pl.BlockSpec((1, G * Rt), lambda: (0, 0)),
        ]
        operands += [fsel, foff]
    scratch = [
        pltpu.VMEM((1, mc, rotp), jnp.float32),   # chunk queries
        pltpu.VMEM((1, rotp), jnp.float32),       # center row
        pltpu.VMEM((1, 1, indl), jnp.float32),    # probe indicator
        pltpu.VMEM((2, Rt, Wb), jnp.uint8),       # code tile slots
        pltpu.VMEM((2, G * Rt), jnp.int32),       # id row slots
        pltpu.VMEM((2, G * Rt), jnp.float32),     # norm row slots
    ]
    if filtered:
        scratch.append(pltpu.VMEM((2, Fbt), jnp.uint8))  # filter slots
    scratch += [
        pltpu.VMEM((mc, _LANES), jnp.float32),    # seg scalars: ⟨q,c⟩
        pltpu.VMEM((mc, _LANES), jnp.float32),    # seg scalars: bias
        pltpu.VMEM((mc, _LANES), jnp.float32),    # bins: best
        pltpu.VMEM((mc, _LANES), jnp.int32),
        pltpu.VMEM((mc, _LANES), jnp.float32),    # bins: second
        pltpu.VMEM((mc, _LANES), jnp.int32),
        pltpu.VMEM((mc, kpad), jnp.float32),      # chunk candidates
        pltpu.VMEM((mc, kpad), jnp.int32),
        pltpu.VMEM((mc, kpad), jnp.float32),      # ring running block
        pltpu.VMEM((mc, kpad), jnp.int32),
        pltpu.VMEM((2, mc, kpad), jnp.float32),   # recv slots
        pltpu.VMEM((2, mc, kpad), jnp.int32),
        pltpu.SemaphoreType.DMA,                  # chunk-query copy
        pltpu.SemaphoreType.DMA((2,)),            # center + indicator
        # code/id/norm (+filter) tile slots
        pltpu.SemaphoreType.DMA((2, 4 if filtered else 3)),
        pltpu.SemaphoreType.DMA((2, 2)),          # ring send
        pltpu.SemaphoreType.DMA((2, 2)),          # ring recv
        pltpu.SemaphoreType.REGULAR((2,)),        # slot capacity
    ]
    out_v, out_i = pl.pallas_call(
        functools.partial(
            _ring_lut_scan_kernel, k=k, n_dev=n_dev, mc=mc, NS=NS,
            n_t=n_t, metric=metric, pq_bits=pq_bits, S=S, P=Pl, G=G,
            Sg=Sg, Kc=Kc, L=L, Rt=Rt, rot=rot, rotp=rotp, indl=indl,
            axis_name=axis_name, flow_control=not interpret,
            filtered=filtered),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((mc, kpad), lambda: (0, 0)),
            pl.BlockSpec((mc, kpad), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mc, kpad), jnp.float32),
            jax.ShapeDtypeStruct((mc, kpad), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(*operands)
    return out_v, out_i
