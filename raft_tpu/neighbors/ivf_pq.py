"""IVF-PQ — inverted-file index with product-quantized residuals.

TPU-native re-design of ``raft::neighbors::ivf_pq``
(ivf_pq-inl.cuh:272 build, :478 search; detail/ivf_pq_build.cuh:1511;
detail/ivf_pq_search.cuh; the compute_similarity scan kernel
detail/ivf_pq_compute_similarity-inl.cuh). Design mapping and the one
deliberate algorithmic change:

- coarse quantizer: balanced kmeans (as the reference, ivf_pq_build.cuh:1618);
- random rotation: QR of a Gaussian (ivf_pq_build.cuh:122) giving an
  orthonormal embedding dim → rot_dim = pq_dim·pq_len;
- codebooks: PER_SUBSPACE kmeans over residual sub-vectors — all pq_dim
  subspace kmeans runs execute as ONE vmapped Lloyd (the reference loops
  subspaces, ivf_pq_build.cuh:404-407);
- storage: padded per-list blocks of uint8 codes (the TPU analog of the
  reference's packed interleaved n-bit lists) + ids;
- **search restructure**: the reference builds a LUT per (query, probe)
  over *residual* distances, then scans packed codes in shared memory.
  A per-(query,probe) LUT is hostile to XLA (dynamic, smem-sized). We
  decompose the asymmetric distance instead:
      ‖q − (c + d)‖² = ‖q‖² − 2⟨q,c⟩ − 2⟨q,d⟩ + ‖c + d‖²
  where d = decoded PQ residual. ‖c+d‖² is a per-candidate scalar
  **precomputed at build**; ⟨q,c⟩ falls out of coarse probing; and
  ⟨q,d⟩ = Σ_s QLUT[s, code_s] needs only a *query-only* LUT
  [pq_dim, 2^bits] built by one batched MXU contraction. The list scan
  is then a pure gather+sum — the Pallas kernel target — with identical
  math to the reference's fused scan.

Supported metrics: sqeuclidean / euclidean / inner_product / cosine
(cosine = inner product over L2-normalized vectors, as the reference).
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core.errors import expects
from raft_tpu.core.tracing import annotate, traced, span
from raft_tpu.core import ids as _ids
from raft_tpu.core import serialize as ser
from raft_tpu.obs import index_stats as _istats
from raft_tpu.obs import spans as _obs_spans
from raft_tpu.robust import degrade as _degrade
from raft_tpu.robust import faults as _faults
from raft_tpu.robust import retry as _retry
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
from raft_tpu.distance.types import DistanceType, resolve_metric
from raft_tpu.matrix import select_k as _select_k
from raft_tpu.utils.precision import get_precision

# Code arrays above this size scan via dynamic_slice (see the
# billion-scale guard in _search_grouped).
_SLICE_SCAN_BYTES = 2 << 30

_SERIAL_VERSION = 2


@dataclasses.dataclass
class IndexParams:
    """reference: ``ivf_pq::index_params`` (ivf_pq_types.hpp:48-148)."""

    n_lists: int = 1024
    metric: str = "sqeuclidean"
    pq_dim: int = 0           # 0 → dim/2 rounded to a multiple of 8 (reference default heuristic)
    pq_bits: int = 8          # 4..8 (codebook size 2^bits)
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    codebook_kind: str = "per_subspace"  # | "per_cluster"
    add_data_on_build: bool = True
    list_size_cap_factor: float = 4.0
    # TPU-specific: cap padded list capacity at list_size_cap_factor ×
    # mean and SPILL overflow rows to their second-nearest list instead
    # of dropping them (ivf_common.spill_assignments). The padded
    # [n_lists, L, ...] layout pays the fattest list's padding on every
    # scan DMA — and at 10⁸ rows overflows HBM outright — so spill with
    # cap_factor ~1.5 trades a marginal assignment-quality loss for a
    # 2-3× smaller scan working set.
    spill: bool = False
    seed: int = 0
    # TPU-specific: keep a bf16 reconstruction (c + decoded residual) of
    # every list alongside the codes. Trades HBM (2 bytes/dim) for scan
    # speed — the grouped scan then skips the per-chunk one-hot decode
    # (expensive at pq_bits=8: the MXU decode runs at K× the lookup
    # FLOPs). "auto" enables it when the cache stays under ~1 GB.
    cache_reconstruction: str = "auto"  # "auto" | "always" | "never"


@dataclasses.dataclass
class SearchParams:
    """reference: ``ivf_pq::search_params``.

    ``scan_mode``: "grouped" is the list-centric batch scan (see
    neighbors/ivf_common.py), "per_query" the gather path for small
    batches, "auto" picks by batch size.

    ``lut_dtype``: dtype the query LUT is quantized to before the scan
    contraction — the reference's ``search_params::lut_dtype`` fp8 option
    (detail/ivf_pq_fp_8bit.cuh) trading LUT precision for on-chip
    footprint. One of "auto" | "float32" | "bfloat16" | "float8_e4m3".
    The Pallas LUT-scan tier applies the same knob to its codebook
    operand (see ops.pallas_kernels.ivfpq_lut_scan_topk). The default
    "auto" resolves per dispatch (:func:`resolve_lut_dtype`): fp8 for
    oversampled scans on TPU — the measured-default trade, recall
    deltas recorded per dataset by the bench lut_dtype legs and held by
    the benchdiff gate — declining to bf16 when the candidate slack is
    too thin to absorb fp8's ranking noise, and exact f32 everywhere
    else.

    ``scan_select`` picks the grouped path's selection engine:
    "exact" (reference semantics), "approx" (TPU hardware top-k,
    recall-targeted; see ivf_flat), or "pallas" — the fused Pallas
    LUT-scan kernel over packed codes (no recon cache needed, candidate
    tables never hit HBM; docs/api_reference.md has the decision
    table). "approx" auto-upgrades to the pallas tier on TPU for
    oversampled shapes (n_probes ≥ 64 or k ≥ 400) when no recon cache
    exists — the configs where the XLA scan's HBM transients are
    hostile. The tier needs n_probes·256 ≥ k; a ``filter_bitset`` rides
    along as a streamed per-candidate mask (packed keep bits beside the
    codes, sentinel-masked before bin selection — filtered searches no
    longer leave the fast path); ineligible explicit requests warn once
    (with the concrete reason) and run the approx tier instead."""

    n_probes: int = 20
    query_tile: int = 64
    scan_mode: str = "auto"  # "auto" | "grouped" | "per_query"
    list_chunk: int = 64
    lut_dtype: str = "auto"  # | "float32" | "bfloat16" | "float8_e4m3"
    # grouped-path per-segment selection: "exact" (reference semantics),
    # "approx" (TPU hardware top-k, recall-targeted; see ivf_flat), or
    # "pallas" (fused LUT-scan kernel over packed codes)
    scan_select: str = "exact"  # | "approx" | "pallas"
    scan_recall: float = 0.95
    # the reference's refinement_rate pattern (refine-inl.cuh) folded
    # into search(): "f32_regen" scans k·refine_ratio candidates, then
    # re-ranks them against exact f32 rows through neighbors.refine's
    # dispatch tier (the fused Pallas gather-refine kernel on TPU
    # oversampled shapes, XLA einsum otherwise). Needs search()'s
    # ``dataset`` argument: a device array (fused-eligible), a host
    # array/memmap (host-gather tier), or a device-chunk provider with
    # ``_block``/``chunk_rows`` (provider-regen tier).
    refine: str = "none"  # | "f32_regen"
    refine_ratio: float = 2.0
    # host-resident re-rank bases only (ISSUE 17): "auto" routes
    # through the tiered candidate-row prefetch pipeline
    # (neighbors.tiered — the host fetch overlapped under the scan)
    # when eligible, falling back to the serialized host gather;
    # "tiered" forces the pipeline (mem guard still applies);
    # "serial" pins refine_gathered — the degrade ladder's last-resort
    # host_gather rung and the bench's comparison leg. Device-resident
    # bases ignore this knob (the fused/XLA tiers need no transfer).
    refine_transfer: str = "auto"  # | "tiered" | "serial"


_LUT_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
               "float8_e4m3": jnp.float8_e4m3fn}

#: Documented recall floor for the fp8-QLUT dispatch default: the
#: recorded per-dataset recall@10 delta of the fp8 legs (bench conf
#: ``lut_dtype`` sweeps, held by the benchdiff gate) must stay within
#: this of the f32 leg at fixed search params. A dataset measured past
#: the floor runs with ``lut_dtype="bfloat16"`` (or f32) explicitly —
#: dispatch cannot see recall at runtime, so the floor's static proxy
#: is candidate slack (:data:`FP8_LUT_MIN_SLACK`): with ≥4× more
#: scanned candidates than requested neighbors, fp8's LUT rounding
#: reorders within the oversample margin, not across the cut.
FP8_LUT_RECALL_FLOOR = 0.01
#: Minimum candidate slack (n_probes·LUT_SCAN_BINS / k) before "auto"
#: picks fp8 over bf16 for an oversampled scan.
FP8_LUT_MIN_SLACK = 4


def resolve_lut_dtype(lut_dtype: str, n_probes: int, k: int,
                      selectivity: float = 1.0) -> str:
    """Resolve ``SearchParams.lut_dtype="auto"`` for one dispatch.

    fp8 QLUTs are the measured default for OVERSAMPLED scans (the
    LUT-tier auto-upgrade shape: n_probes ≥ 64 or k ≥ 400) on TPU —
    the reference's fp8 trade (ivf_pq_fp_8bit.cuh) promoted from
    opt-in to default where the recall cost is bounded (see
    :data:`FP8_LUT_RECALL_FLOOR`). When the candidate slack is under
    :data:`FP8_LUT_MIN_SLACK`, dispatch declines to bf16 instead; every
    other shape keeps exact f32.

    ``selectivity`` (set-bit fraction of a ``filter_bitset``, 1.0
    unfiltered — :func:`_filter_selectivity`) discounts the slack: a
    filtered scan's bins hold only SURVIVING candidates, so the
    effective oversample margin fp8's ranking noise must stay inside is
    ``selectivity · n_probes · LUT_SCAN_BINS`` — at 1% selectivity a
    nominally 25× slack is really 0.25× and fp8 reordering would cross
    the cut, so dispatch declines to bf16.

    ``RAFT_TPU_FP8_LUT`` = auto | on | off
    (tri-state): "on" applies the policy off-TPU too (interpret-mode
    tests), "off" pins auto to f32. Explicit dtypes pass through
    untouched; each auto resolution lands in
    ``ivf_pq.lut.dispatch{dtype=...}``."""
    if lut_dtype != "auto":
        return lut_dtype
    from raft_tpu.ops import pallas_kernels as _pk

    force = _obs_spans.env_tristate("RAFT_TPU_FP8_LUT")
    oversampled = n_probes >= 64 or k >= 400
    chosen = "float32"
    if (force != "off" and oversampled
            and (force == "on" or _pk._on_tpu())):
        surviving = selectivity * n_probes * _pk.LUT_SCAN_BINS
        slack_ok = surviving >= FP8_LUT_MIN_SLACK * k
        chosen = "float8_e4m3" if slack_ok else "bfloat16"
    if _obs_spans.enabled():
        _obs_spans.registry().inc("ivf_pq.lut.dispatch",
                                  labels={"dtype": chosen})
    return chosen


def _filter_selectivity(filter_bits) -> float:
    """Eager set-bit-fraction estimate of a filter bitset feeding the
    fp8-LUT slack discount (one tiny popcount reduction + host sync per
    filtered dispatch with ``lut_dtype="auto"``). Returns 1.0 for no
    filter. Under an abstract trace (a jitted ``search`` call, the
    eval_shape capacity prover) the popcount cannot concretize — the
    filter IS present but its density is unknowable, so return 0.0:
    the slack check then declines fp8 to bf16, the conservative side
    of the precision policy (a 1.0 fallback would silently disable the
    discount exactly when a selective filter needs it)."""
    if filter_bits is None:
        return 1.0
    from raft_tpu.core import bitset as _bitset

    try:
        return float(_bitset.density(filter_bits))
    except (jax.errors.ConcretizationTypeError, TypeError):
        return 0.0


def _quantize_lut(lut: jax.Array, lut_dtype: str) -> jax.Array:
    """Round the query LUT to the requested storage dtype, returning it in
    a compute-friendly dtype (fp8 simulates the reference's fp8 LUT: the
    values are quantized, the contraction runs in bf16)."""
    expects(lut_dtype in _LUT_DTYPES, "unknown lut_dtype %s", lut_dtype)
    dt = _LUT_DTYPES[lut_dtype]
    if dt == jnp.float32:
        return lut
    q = lut.astype(dt)
    return q.astype(jnp.bfloat16) if dt == jnp.float8_e4m3fn else q


class IvfPqIndex(flax.struct.PyTreeNode):
    """IVF-PQ index (reference: ``ivf_pq::index``, ivf_pq_types.hpp).

    ``codebooks`` is [pq_dim, K, pq_len] for per_subspace codebooks and
    [n_lists, K, pq_len] for per_cluster (ivf_pq_types.hpp:43,83).
    ``packed_codes`` stores n-bit codes bit-packed into bytes — pq_bits=4
    costs half the bytes of pq_bits=8, matching the reference's packed
    list layout (ivf_pq_types.hpp:68)."""

    centers: jax.Array        # [n_lists, dim] f32 (original space)
    centers_rot: jax.Array    # [n_lists, rot_dim] f32
    rotation: jax.Array       # [rot_dim, dim] f32, orthonormal rows' columns
    codebooks: jax.Array      # [S|n_lists, 2^bits, pq_len] f32
    packed_codes: jax.Array   # [n_lists, L, ceil(pq_dim·pq_bits/8)] u8
    packed_ids: jax.Array     # [n_lists, L] i32, -1 pad
    packed_norms: jax.Array   # [n_lists, L] f32: ‖c + decoded‖²
    list_sizes: jax.Array     # [n_lists] i32
    packed_recon: Optional[jax.Array] = None  # [n_lists, L, rot_dim] bf16 cache
    metric: str = flax.struct.field(pytree_node=False, default="sqeuclidean")
    codebook_kind: str = flax.struct.field(pytree_node=False,
                                           default="per_subspace")
    pq_bits: int = flax.struct.field(pytree_node=False, default=8)
    # 0 → derive from packed_codes (legacy byte-per-subspace layout)
    pq_dim_static: int = flax.struct.field(pytree_node=False, default=0)
    # folded code storage: [n_lists, L·nb/128, 128] instead of
    # [n_lists, L, nb]. A u8 array's trailing dim pads to 128 lanes in
    # TPU tile layouts, so nb=64-byte code rows would occupy 2× their
    # bytes in HBM — at 100M rows the difference between a 9.7 GB and a
    # 19 GB resident index. Row-major bytes are identical either way;
    # codes_chunk() unfolds per scanned chunk.
    codes_folded: bool = flax.struct.field(pytree_node=False, default=False)

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def pq_dim(self) -> int:
        return self.pq_dim_static or self.packed_codes.shape[2]

    @property
    def pq_len(self) -> int:
        return self.codebooks.shape[2]

    @property
    def pq_book_size(self) -> int:
        return self.codebooks.shape[1]

    @property
    def max_list_size(self) -> int:
        return self.packed_ids.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_sizes))

    def codes_chunk(self, sl) -> jax.Array:
        """[C, L, nb] code rows for the list chunk ``sl`` — unfolds the
        lane-folded storage (see ``codes_folded``)."""
        c = self.packed_codes[sl]
        if self.codes_folded:
            return c.reshape(c.shape[0], self.packed_ids.shape[1], -1)
        return c

    def unpack_codes(self, packed: jax.Array) -> jax.Array:
        """[..., nbytes] u8 → [..., pq_dim] u8 code values."""
        return unpack_bits(packed, self.pq_dim, self.pq_bits)


# ---------------------------------------------------------------------------
# n-bit code packing (reference: packed n-bit lists, ivf_pq_types.hpp:68)
# ---------------------------------------------------------------------------

def packed_nbytes(pq_dim: int, pq_bits: int) -> int:
    return (pq_dim * pq_bits + 7) // 8


def pack_bits_np(codes: np.ndarray, pq_bits: int) -> np.ndarray:
    """Host bit-pack [n, S] u8 code values (< 2^pq_bits) → [n, nbytes] u8."""
    if pq_bits == 8:
        return np.ascontiguousarray(codes, dtype=np.uint8)
    n, S = codes.shape
    nbytes = packed_nbytes(S, pq_bits)
    out = np.zeros((n, nbytes), np.uint8)
    for s in range(S):
        byte_idx, off = divmod(s * pq_bits, 8)
        v = codes[:, s].astype(np.uint16) << off
        out[:, byte_idx] |= (v & 0xFF).astype(np.uint8)
        if byte_idx + 1 < nbytes:
            out[:, byte_idx + 1] |= (v >> 8).astype(np.uint8)
    return out


def pack_bits(codes: jax.Array, pq_bits: int) -> jax.Array:
    """Device bit-pack [..., S] u8 → [..., nbytes] u8 (jit-safe; the SPMD
    build packs on device where a host round-trip is impossible)."""
    if pq_bits == 8:
        return codes.astype(jnp.uint8)
    S = codes.shape[-1]
    nbytes = packed_nbytes(S, pq_bits)
    acc = jnp.zeros(codes.shape[:-1] + (nbytes,), jnp.uint16)
    for s in range(S):  # static unroll: S is a trace-time constant
        byte_idx, off = divmod(s * pq_bits, 8)
        v = codes[..., s].astype(jnp.uint16) << off
        acc = acc.at[..., byte_idx].set(acc[..., byte_idx] | (v & 0xFF))
        if byte_idx + 1 < nbytes:
            acc = acc.at[..., byte_idx + 1].set(
                acc[..., byte_idx + 1] | (v >> 8))
    return acc.astype(jnp.uint8)


def unpack_bits(packed: jax.Array, pq_dim: int, pq_bits: int) -> jax.Array:
    """Device unpack [..., nbytes] u8 → [..., pq_dim] u8 code values.
    Pure shift/mask VPU ops — fuses into whatever consumes the codes."""
    if pq_bits == 8:
        return packed
    nbytes = packed.shape[-1]
    s = np.arange(pq_dim)
    byte_idx = (s * pq_bits) // 8
    # full-rank (1, ..., pq_dim) operands: the sanitize lane runs with
    # jax_numpy_rank_promotion="raise", so 1-D-vs-N-D broadcasts are
    # spelled out instead of implied
    lead = (1,) * (packed.ndim - 1)
    bit_off = jnp.asarray(((s * pq_bits) % 8).reshape(lead + (-1,)),
                          jnp.uint16)
    p16 = packed.astype(jnp.uint16)
    lo = jnp.take(p16, jnp.asarray(byte_idx), axis=-1)
    hi_idx = np.minimum(byte_idx + 1, nbytes - 1)
    hi = jnp.take(p16, jnp.asarray(hi_idx), axis=-1)
    hi = jnp.where(
        jnp.asarray((byte_idx + 1 < nbytes).reshape(lead + (-1,))), hi, 0)
    val = ((lo | (hi << 8)) >> bit_off) & ((1 << pq_bits) - 1)
    return val.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

def _default_pq_dim(dim: int) -> int:
    """Reference heuristic (ivf_pq_types.hpp pq_dim=0 doc): ~dim/2, rounded
    to a multiple of 8, at least 8."""
    return max(8, (dim // 2 + 7) // 8 * 8 if dim >= 16 else dim)


def make_rotation_matrix(key: jax.Array, rot_dim: int, dim: int) -> jax.Array:
    """Random orthonormal embedding R [rot_dim, dim], RᵀR = I_dim
    (reference: make_rotation_matrix, ivf_pq_build.cuh:122 — QR of a
    Gaussian). Rotation preserves inner products and L2 distances."""
    g = jax.random.normal(key, (rot_dim, dim), jnp.float32)
    q, _ = jnp.linalg.qr(g, mode="reduced")  # [rot_dim, dim] for rot_dim>=dim
    return q


@partial(jax.jit, static_argnames=("k", "n_iters"))
def _vmapped_lloyd(data, k: int, n_iters: int, key):
    """Independent kmeans per subspace, one vmapped program
    (reference loops kmeans_balanced per subspace, ivf_pq_build.cuh:404)."""
    S, n, d = data.shape

    def one(sub_data, subkey):
        idx = jax.random.choice(subkey, n, (k,), replace=False)
        c0 = sub_data[idx]

        def body(i, c):
            d2 = (jnp.sum(sub_data**2, 1)[:, None] + jnp.sum(c**2, 1)[None, :]
                  - 2.0 * jnp.matmul(sub_data, c.T, precision=get_precision()))
            labels = jnp.argmin(d2, axis=1)
            sums = jax.ops.segment_sum(sub_data, labels, num_segments=k)
            counts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), labels,
                                         num_segments=k)
            return jnp.where(counts[:, None] > 0,
                             sums / jnp.maximum(counts[:, None], 1e-12), c)

        return lax.fori_loop(0, n_iters, body, c0)

    keys = jax.random.split(key, S)
    return jax.vmap(one)(data, keys)


@partial(jax.jit, static_argnames=("k", "n_iters"))
def _vmapped_lloyd_masked(data, mask, k: int, n_iters: int, key):
    """Independent kmeans per cluster over PADDED row blocks — the
    per_cluster codebook trainer (reference: train_per_cluster,
    ivf_pq_build.cuh:448-492). ``mask`` zero-weights pad rows; clusters
    with fewer than k valid rows keep their init centroids for the
    surplus entries."""
    C, cap, d = data.shape

    def one(sub, m, subkey):
        w = m.astype(jnp.float32)
        p = w / jnp.maximum(jnp.sum(w), 1.0)
        idx = jax.random.choice(subkey, cap, (k,), replace=False, p=p)
        c0 = sub[idx]

        def body(i, c):
            d2 = (jnp.sum(sub**2, 1)[:, None] + jnp.sum(c**2, 1)[None, :]
                  - 2.0 * jnp.matmul(sub, c.T, precision=get_precision()))
            labels = jnp.argmin(d2, axis=1)
            sums = jax.ops.segment_sum(sub * w[:, None], labels,
                                       num_segments=k)
            counts = jax.ops.segment_sum(w, labels, num_segments=k)
            return jnp.where(counts[:, None] > 0,
                             sums / jnp.maximum(counts[:, None], 1e-12), c)

        return lax.fori_loop(0, n_iters, body, c0)

    keys = jax.random.split(key, C)
    return jax.vmap(one)(data, mask, keys)


def _train_per_cluster(tr_res: jax.Array, tr_labels: jax.Array,
                       n_lists: int, pq_dim: int, pq_len: int, K: int,
                       n_iters: int, key) -> jax.Array:
    """Per-cluster codebooks [n_lists, K, pq_len]: each cluster trains one
    codebook over its residual sub-vectors pooled across ALL subspaces
    (ivf_pq_types.hpp:83 PER_CLUSTER). Rows are grouped per cluster with
    the same sort+scatter the list packers use; clusters hotter than the
    per-cluster cap are subsampled by truncation (the trainset is already
    a random subsample, so truncation is unbiased)."""
    from raft_tpu.neighbors import ivf_common as ic

    n_train = tr_res.shape[0]
    flat_sub = tr_res.reshape(n_train * pq_dim, pq_len)
    flat_lbl = jnp.repeat(tr_labels.astype(jnp.int32), pq_dim)
    avg = max(1, (n_train * pq_dim) // max(n_lists, 1))
    # clamp: the padded block is [n_lists, cap, pq_len] whose tiny minor
    # dim lane-pads to 128 — an unbounded cap at large n_lists would
    # blow HBM for no statistical gain
    cap = min(max(2 * K, -(-4 * avg // 8) * 8), max(2 * K, 8192))
    (packed,), _, sizes, _, _ = ic.pack_lists(
        (flat_sub,), flat_lbl,
        jnp.arange(n_train * pq_dim, dtype=jnp.int32),
        n_lists, cap, (jnp.float32(0),))
    mask = jnp.arange(cap)[None, :] < sizes[:, None]
    return _vmapped_lloyd_masked(packed, mask, K, n_iters, key)


def _encode_rows(rot_rows: jax.Array, centers_rot: jax.Array,
                 labels: jax.Array, codebooks: jax.Array,
                 block: int = 4096) -> jax.Array:
    """PQ-encode rotated rows against their cluster's residual
    (reference: encode+pack, ivf_pq_build.cuh:1411-1432).
    Returns codes [n, pq_dim] uint8."""
    S, K, P = codebooks.shape
    n = rot_rows.shape[0]

    def encode_block(args):
        rows, lbls = args
        res = rows - centers_rot[lbls]                    # [b, rot_dim]
        sub = res.reshape(res.shape[0], S, P)             # [b, S, P]
        # ‖sub − cb‖² argmin over K: [b, S, K]
        d2 = (jnp.sum(sub**2, -1)[..., None]
              + jnp.sum(codebooks**2, -1)[None]
              - 2.0 * jnp.einsum("bsp,skp->bsk", sub, codebooks,
                                 precision=get_precision()))
        return jnp.argmin(d2, axis=-1).astype(jnp.uint8)  # [b, S]

    if n <= block:
        return encode_block((rot_rows, labels))
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    rows_p = jnp.pad(rot_rows, ((0, pad), (0, 0)))
    lbls_p = jnp.pad(labels, (0, pad))
    out = lax.map(encode_block, (rows_p.reshape(n_blocks, block, -1),
                                 lbls_p.reshape(n_blocks, block)))
    return out.reshape(n_blocks * block, S)[:n]


def _encode_rows_cluster(rot_rows: jax.Array, centers_rot: jax.Array,
                         labels: jax.Array, codebooks: jax.Array,
                         block: int = 4096) -> jax.Array:
    """Per-cluster encode: row i's subspaces all quantize against its
    cluster's codebook ``codebooks[labels[i]]`` (reference: PER_CLUSTER
    encode, ivf_pq_build.cuh). Returns codes [n, pq_dim] uint8."""
    C, K, P = codebooks.shape
    n = rot_rows.shape[0]
    S = rot_rows.shape[1] // P

    def encode_block(args):
        rows, lbls = args
        res = rows - centers_rot[lbls]
        sub = res.reshape(res.shape[0], S, P)
        cb = codebooks[lbls]                              # [b, K, P]
        d2 = (jnp.sum(sub**2, -1)[..., None]
              + jnp.sum(cb**2, -1)[:, None, :]
              - 2.0 * jnp.einsum("bsp,bkp->bsk", sub, cb,
                                 precision=get_precision()))
        return jnp.argmin(d2, axis=-1).astype(jnp.uint8)

    if n <= block:
        return encode_block((rot_rows, labels))
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    rows_p = jnp.pad(rot_rows, ((0, pad), (0, 0)))
    lbls_p = jnp.pad(labels, (0, pad))
    out = lax.map(encode_block, (rows_p.reshape(n_blocks, block, -1),
                                 lbls_p.reshape(n_blocks, block)))
    return out.reshape(n_blocks * block, S)[:n]


def _decode_dtype():
    """One-hot decode compute dtype: bf16 feeds the MXU on TPU; CPU XLA
    doesn't fuse the one-hot, so keep exact f32 there."""
    return jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16


def _decode_codes_cluster(codes: jax.Array, cb_rows: jax.Array) -> jax.Array:
    """Per-cluster decode: codes [..., S] u8 with a MATCHING per-row
    codebook ``cb_rows [..., K, P]`` → decoded residuals [..., S·P] f32."""
    K, P = cb_rows.shape[-2:]
    S = codes.shape[-1]
    dt = _decode_dtype()
    oh = jax.nn.one_hot(codes.astype(jnp.int32), K, dtype=dt)
    dec = jnp.einsum("...sk,...kp->...sp", oh, cb_rows.astype(dt),
                     preferred_element_type=jnp.float32)
    return dec.reshape(*codes.shape[:-1], S * P)


def _decode_lists_cluster(codes: jax.Array, cb: jax.Array) -> jax.Array:
    """Per-cluster decode of a chunk of packed LISTS: codes [C, L, S] u8
    with one codebook per list ``cb [C, K, P]`` → [C, L, S·P] f32 (the
    recon cache and the grouped scan both decode in this shape)."""
    C, L, S = codes.shape
    dt = _decode_dtype()
    oh = jax.nn.one_hot(codes.astype(jnp.int32), cb.shape[1], dtype=dt)
    dec = jnp.einsum("clsk,ckp->clsp", oh, cb.astype(dt),
                     preferred_element_type=jnp.float32)
    return dec.reshape(C, L, -1)


def _decode_codes(codes: jax.Array, codebooks: jax.Array) -> jax.Array:
    """codes [..., S] u8 → decoded residuals [..., S*P] f32.

    On TPU the lookup is a one-hot MXU contraction: arbitrary-axis
    gathers do not lower on the TPU backend (and would be VPU-serial
    anyway), while the iota-compare one-hot feeds the MXU directly.
    CPU keeps the natural gather."""
    S, K, P = codebooks.shape
    if jax.default_backend() == "cpu":
        gathered = codebooks[jnp.arange(S), codes.astype(jnp.int32)]
        return gathered.reshape(*codes.shape[:-1], S * P)
    oh = jax.nn.one_hot(codes.astype(jnp.int32), K, dtype=jnp.bfloat16)
    dec = jnp.einsum("...sk,skp->...sp", oh, codebooks.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return dec.reshape(*codes.shape[:-1], S * P)


def _stable_slots(labels: np.ndarray, n_lists: int,
                  base: Optional[np.ndarray] = None):
    """Each row's (list, slot) address from ONE stable sort — the shared
    core of every host packer (reference: encode+pack,
    ivf_pq_build.cuh:1411-1432). ``base`` offsets slots by current list
    fill (extend / chunked append). Returns (order, sorted_l, slot):
    row ``order[i]`` goes to ``(sorted_l[i], slot[i])``."""
    n = len(labels)
    order = np.argsort(labels, kind="stable")
    sorted_l = labels[order]
    # tolerate the spill drop marker (label == n_lists): those rows
    # rank within their own group and every caller's slot/keep mask
    # rejects them via ``sorted_l < n_lists``
    starts = np.searchsorted(sorted_l, np.arange(n_lists + 1))
    rank = np.arange(n) - starts[sorted_l]
    slot = rank if base is None else base[np.clip(sorted_l, 0,
                                                  n_lists - 1)] + rank
    return order, sorted_l, slot


def _pack_codes(codes: np.ndarray, labels: np.ndarray, norms: np.ndarray,
                n_lists: int, max_list_size: int, row_ids: np.ndarray):
    """Vectorized list packing: one argsort + fancy-indexed fill
    (reference: encode+pack, ivf_pq_build.cuh:1411-1432)."""
    n, S = codes.shape
    order, sorted_labels, rank = _stable_slots(labels, n_lists)
    keep = rank < max_list_size
    dropped = int(n - keep.sum())
    packed = np.zeros((n_lists, max_list_size, S), np.uint8)
    # id-table width follows the incoming global ids (core.ids policy:
    # int32 until the row count demands int64, never narrowed here)
    ids = np.full((n_lists, max_list_size), -1,
                  _ids.np_id_dtype_like(row_ids))
    pnorm = np.zeros((n_lists, max_list_size), np.float32)
    rows = order[keep]
    ls, rk = sorted_labels[keep], rank[keep]
    packed[ls, rk] = codes[rows]
    ids[ls, rk] = row_ids[rows]
    pnorm[ls, rk] = norms[rows]
    sizes = np.minimum(np.bincount(labels, minlength=n_lists),
                       max_list_size).astype(np.int32)
    if dropped:
        from raft_tpu.core import logging as _log
        _log.warn("ivf_pq: dropped %d overflow vectors", dropped)
    return packed, ids, pnorm, sizes


def _train_quantizers(trainset: jax.Array, params: IndexParams, dim: int,
                      pq_dim: int, pq_len: int, K: int, key,
                      km: KMeansBalancedParams,
                      max_codebook_rows: int = 1 << 16,
                      centers: Optional[jax.Array] = None):
    """Coarse centers + rotation + codebooks from a (sub)trainset — the
    quantizer-training block shared by build() and build_chunked()
    (reference: detail/ivf_pq_build.cuh:1511-1621 + :385-492).

    Codebook training sees at most ``max_codebook_rows`` rows (a strided
    subset of the already-random trainset; the coarse kmeans keeps the
    full trainset). Beyond the statistics (≥256 samples/centroid at
    K=256), this bounds a TPU-specific blowup: the per-subspace sample
    [pq_dim, n, pq_len] lane-pads its tiny minor dim to 128, so an
    uncapped 2M-row trainset at pq_len=2 would demand 64× its logical
    size in HBM (measured: a 51 GB allocation on a 16 GB chip).

    ``centers`` (optional) skips the coarse fit and trains the
    rotation/codebooks against the GIVEN coarse centers — the
    distributed build's ``coarse="distributed"`` mode fits its centers
    with the psum-Lloyd MNMG trainer first, and the codebooks must see
    residuals to the centers the index will actually encode against."""
    n_train = trainset.shape[0]
    rot_dim = pq_dim * pq_len
    if centers is None:
        centers = kmeans_balanced.fit(trainset, params.n_lists, km)
    rotation = make_rotation_matrix(jax.random.fold_in(key, 1), rot_dim, dim)
    centers_rot = jnp.matmul(centers, rotation.T, precision=get_precision())
    stride = max(1, -(-n_train // max_codebook_rows))
    tr_cb = trainset[::stride]
    n_cb = tr_cb.shape[0]
    cb_labels = kmeans_balanced.predict(centers, tr_cb, km)
    tr_res = (jnp.matmul(tr_cb, rotation.T, precision=get_precision())
              - centers_rot[cb_labels])
    if params.codebook_kind == "per_subspace":
        sub = jnp.transpose(tr_res.reshape(n_cb, pq_dim, pq_len), (1, 0, 2))
        codebooks = _vmapped_lloyd(sub, K, params.kmeans_n_iters,
                                   jax.random.fold_in(key, 2))
    else:
        codebooks = _train_per_cluster(
            tr_res, cb_labels, params.n_lists, pq_dim, pq_len, K,
            params.kmeans_n_iters, jax.random.fold_in(key, 2))
    return centers, rotation, centers_rot, codebooks


def _encode_with_norms(x_rot: jax.Array, centers_rot: jax.Array,
                       labels: jax.Array, codebooks: jax.Array,
                       codebook_kind: str, block: int = 4096):
    """(codes [n, S] u8, ‖c + decoded‖² [n]) for either codebook kind —
    the encode block shared by build/build_chunked/extend. Both the
    encode and the norms decode are blocked with ``lax.map``: an
    unblocked decode's one-hot is K× the code volume (measured OOM at
    n=1M, pq_dim=64, K=256 on a 16 GB chip)."""
    per_subspace = codebook_kind == "per_subspace"
    if per_subspace:
        codes = _encode_rows(x_rot, centers_rot, labels, codebooks)
    else:
        codes = _encode_rows_cluster(x_rot, centers_rot, labels, codebooks)

    def norms_block(args):
        cds, lbls = args
        if per_subspace:
            dec = _decode_codes(cds, codebooks)
        else:
            dec = _decode_codes_cluster(cds, codebooks[lbls])
        rec = centers_rot[lbls] + dec
        return jnp.sum(rec * rec, axis=1)

    n = codes.shape[0]
    if n <= block:
        return codes, norms_block((codes, labels))
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    codes_p = jnp.pad(codes, ((0, pad), (0, 0)))
    lbls_p = jnp.pad(labels, (0, pad))
    norms = lax.map(norms_block,
                    (codes_p.reshape(n_blocks, block, -1),
                     lbls_p.reshape(n_blocks, block)))
    return codes, norms.reshape(-1)[:n]


@traced("raft_tpu.ivf_pq.build")
def build(dataset: jax.Array, params: Optional[IndexParams] = None) -> IvfPqIndex:  # graftlint: disable-fn=GL01 (host-side histogram/pack by design)
    """Build the index (reference: ivf_pq::build, detail/ivf_pq_build.cuh:1511)."""
    if params is None:
        params = IndexParams()
    mt = resolve_metric(params.metric)
    expects(params.codebook_kind in ("per_subspace", "per_cluster"),
            "codebook_kind must be per_subspace or per_cluster")
    expects(4 <= params.pq_bits <= 8, "pq_bits must be in [4, 8]")

    x = jnp.asarray(dataset, jnp.float32)
    n, dim = x.shape
    spherical = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)
    if mt == DistanceType.CosineExpanded:
        x = x / jnp.sqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-12))

    pq_dim = params.pq_dim or _default_pq_dim(dim)
    pq_len = -(-dim // pq_dim)
    rot_dim = pq_dim * pq_len
    K = 1 << params.pq_bits
    key = jax.random.PRNGKey(params.seed)

    # 1. coarse centers (balanced kmeans on a trainset subsample)
    n_train = min(n, max(params.n_lists * 4,
                         int(n * params.kmeans_trainset_fraction)))
    if n_train < n:
        rng = np.random.default_rng(params.seed)
        tr = jnp.asarray(np.sort(rng.choice(n, n_train, replace=False)))
        trainset = x[tr]
    else:
        trainset = x
    km = KMeansBalancedParams(n_iters=params.kmeans_n_iters,
                              metric="cosine" if spherical else "l2",
                              seed=params.seed)
    # 2.-3. coarse centers + rotation + codebooks (shared trainer)
    with span("train") as _sp:
        centers, rotation, centers_rot, codebooks = _train_quantizers(
            trainset, params, dim, pq_dim, pq_len, K, key, km)
        _sp.attach(centers_rot, codebooks)

    avg = max(1, n // params.n_lists)
    nbytes = packed_nbytes(pq_dim, params.pq_bits)

    if not params.add_data_on_build:
        max_list_size = max(8, int(avg * params.list_size_cap_factor))
        return IvfPqIndex(
            centers=centers, centers_rot=centers_rot, rotation=rotation,
            codebooks=codebooks,
            packed_codes=jnp.zeros((params.n_lists, max_list_size, nbytes), jnp.uint8),
            packed_ids=jnp.full((params.n_lists, max_list_size), -1, jnp.int32),
            packed_norms=jnp.zeros((params.n_lists, max_list_size), jnp.float32),
            list_sizes=jnp.zeros((params.n_lists,), jnp.int32),
            metric=mt.value, codebook_kind=params.codebook_kind,
            pq_bits=params.pq_bits, pq_dim_static=pq_dim)

    # 4. encode + bit-pack + pack all rows into lists — ON DEVICE (same
    # pack the distributed build uses); only the [n_lists] histogram
    # round-trips the host to size the static padded capacity
    from raft_tpu.neighbors.ivf_flat import _fit_list_size, _lane_round
    from raft_tpu.neighbors import ivf_common as ic

    with span("assign") as _sp:
        if params.spill:
            # cap capacity + cascade overflow to next-nearest lists (see
            # IndexParams.spill); encode AFTER spilling so residuals use
            # the assigned list's center
            lk = kmeans_balanced.predict_topk(centers, x, ic.SPILL_DEPTH, km)
            max_list_size = _lane_round(
                int(avg * params.list_size_cap_factor))
            labels = ic.spill_assignments(lk[:, 0], lk[:, 1],
                                          params.n_lists, max_list_size,
                                          *[lk[:, c] for c in
                                            range(2, lk.shape[1])])
            n_marker = int(jnp.sum(labels >= params.n_lists))
            if n_marker:
                # pack_lists' drop counter excludes out-of-range labels
                from raft_tpu.core import logging as _log
                _log.warn("ivf_pq: %d rows overflowed every spill choice at "
                          "cap %d (raise list_size_cap_factor)",
                          n_marker, max_list_size)
                ic.note_rows_dropped("ivf_pq", n_marker)
        else:
            labels = kmeans_balanced.predict(centers, x, km)
            # histogram on host: the [n] labels transfer is small, and a
            # device scatter-add histogram serializes on TPU
            counts = np.bincount(np.asarray(labels),
                                 minlength=params.n_lists)
            max_list_size = _fit_list_size(counts, avg,
                                           params.list_size_cap_factor)
        _sp.attach(labels)
    with span("encode") as _sp:
        codes, norms = _encode_with_norms(
            jnp.matmul(x, rotation.T, precision=get_precision()),
            centers_rot,
            jnp.clip(labels, 0, params.n_lists - 1), codebooks,
            params.codebook_kind)
        codes_p = pack_bits(codes, params.pq_bits)
        _sp.attach(codes_p, norms)
    with span("pack") as _sp:
        (packed, pnorm), ids, sizes, dropped, _ = ic.pack_lists_jit(
            [codes_p, norms], labels, _ids.make_ids(n),
            n_lists=params.n_lists, L=max_list_size,
            fill_values=[jnp.zeros((), jnp.uint8),
                         jnp.zeros((), jnp.float32)])
        _sp.attach(packed, ids)
    n_drop = int(dropped)
    if n_drop:
        from raft_tpu.core import logging as _log
        _log.warn("ivf_pq: dropped %d overflow vectors (raise "
                  "list_size_cap_factor)", n_drop)
        ic.note_rows_dropped("ivf_pq", n_drop)
    index = IvfPqIndex(
        centers=centers, centers_rot=centers_rot, rotation=rotation,
        codebooks=codebooks, packed_codes=packed,
        packed_ids=ids, packed_norms=pnorm,
        list_sizes=sizes, metric=mt.value,
        codebook_kind=params.codebook_kind, pq_bits=params.pq_bits,
        pq_dim_static=pq_dim)
    if _want_recon_cache(params, params.n_lists, max_list_size, rot_dim):
        with span("recon_cache") as _sp:
            recon = _build_recon_cache(index)
            _sp.attach(recon)
            index = index.replace(packed_recon=recon)
    ic.note_list_fill("ivf_pq", sizes, max_list_size)
    _istats.note_index_stats(index, name="ivf_pq.build", cheap=True)
    return index


def _count_resume(name: str, value: float = 1.0) -> None:
    """``resume.*{site=ivf_pq.build_chunked}`` counters — recorded only
    when obs is on (the count_dispatch convention)."""
    if _obs_spans.enabled():
        _obs_spans.registry().inc(name, value,
                                  labels={"site": "ivf_pq.build_chunked"})


@traced("raft_tpu.ivf_pq.build_chunked")
def build_chunked(dataset, params: Optional[IndexParams] = None,  # graftlint: disable-fn=GL01 (streaming memmap build syncs per chunk by design)
                  chunk_rows: int = 1 << 18,
                  max_train_rows: int = 1 << 21,
                  progress: bool = False,
                  checkpoint_dir: Optional[str] = None,
                  resume=False) -> IvfPqIndex:
    """Build from a host array/memmap in O(chunk) device + host working
    memory — the billion-scale path (reference: the bench harness's
    memmapped BinFile + subset datasets, cpp/bench/ann/src/common/
    dataset.hpp, and ivf_pq::build's trainset subsampling).

    ``dataset`` may be a ``np.memmap`` (see bench.dataset.bin_memmap):
    rows are touched once per pass (train-sample, label, encode), so host
    RSS stays bounded by ``chunk_rows`` plus the packed index itself.
    ``progress`` prints phase/chunk timings (hour-scale 10⁸-row builds
    are opaque without them).

    **Checkpointed resumable builds** (docs/developer_guide.md
    "Robustness"): with ``checkpoint_dir=`` the build writes a durable
    manifest (atomic tmp+fsync+rename), the trained quantizer state,
    the label pass, and one encoded-list shard per completed chunk.
    ``resume=True`` verifies the manifest's dataset/params fingerprints
    (a mismatch, truncated manifest, or missing shard refuses with a
    clear error) and continues from the last complete chunk — quantizers
    and labels are *loaded*, completed chunks replay from their shards,
    so the resumed index is bit-identical to an uninterrupted build.
    ``resume="auto"`` resumes when a manifest exists and starts fresh
    otherwise. Host reads / device transfers retry under
    :data:`raft_tpu.robust.retry.IO_POLICY`; an encode chunk that hits
    RESOURCE_EXHAUSTED is halved (``degrade.steps`` counts the walk).
    """
    import time as _time

    _t0 = _time.time()

    def _say(msg):
        if progress:
            print(f"[build_chunked +{_time.time()-_t0:7.0f}s] {msg}",
                  flush=True)
    if params is None:
        params = IndexParams()
    mt = resolve_metric(params.metric)
    expects(params.codebook_kind in ("per_subspace", "per_cluster"),
            "codebook_kind must be per_subspace or per_cluster")
    expects(4 <= params.pq_bits <= 8, "pq_bits must be in [4, 8]")
    expects(resume in (False, True, "auto"),
            "resume must be False, True, or 'auto' (got %r)", resume)
    expects(not resume or checkpoint_dir is not None,
            "resume=%r needs checkpoint_dir= (there is no manifest to "
            "resume from without one)", resume)
    n, dim = dataset.shape

    # checkpoint bootstrap: fingerprint the inputs, load + validate the
    # manifest when resuming (robust.checkpoint owns the refusal cases)
    ck = manifest = None
    base_manifest = {}
    if checkpoint_dir is not None:
        from raft_tpu.robust import checkpoint as _ckpt

        ck = _ckpt.BuildCheckpoint(checkpoint_dir)
        # fingerprint ONCE (timed) and thread the pair through every
        # manifest write below — a memmap fingerprint samples real
        # content, so re-fingerprinting per state change would pay the
        # head/tail reads over and over; the elapsed seconds are
        # stamped so long builds can see the identity check's cost
        ds_sha, p_sha, fp_s = _ckpt.fingerprints_once(
            dataset, {**dataclasses.asdict(params),
                      "chunk_rows": chunk_rows,
                      "max_train_rows": max_train_rows})
        base_manifest = {"dataset_sha": ds_sha, "params_sha": p_sha,
                         "fingerprint_s": round(fp_s, 6),
                         "n": int(n), "dim": int(dim),
                         "chunk_rows": int(chunk_rows),
                         "n_chunks": -(-n // chunk_rows)}
        if resume is True or (resume == "auto"
                              and os.path.exists(ck.manifest_path)):
            manifest = ck.load_manifest()
            ck.validate_manifest(manifest, ds_sha, p_sha)
            _count_resume("resume.attempts")
            _say(f"resuming from {ck.manifest_path} "
                 f"(phase {manifest.get('phase')}, "
                 f"{manifest.get('chunks_done', 0)} chunks done)")
    spherical = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)
    normalize = mt == DistanceType.CosineExpanded

    def to_device(rows):
        # device-chunk providers (bench.dataset.DeviceSyntheticChunks)
        # hand back arrays already on device — don't round-trip them
        if isinstance(rows, jax.Array):
            x = rows.astype(jnp.float32)
        else:
            x = jnp.asarray(np.asarray(rows, np.float32))
        if normalize:
            x = x / jnp.sqrt(jnp.maximum(
                jnp.sum(x * x, -1, keepdims=True), 1e-12))
        return x

    def read_chunk(a, b):
        """One host read + device transfer under the shared IO retry
        policy (transient transfer stalls and flaky memmap reads recover
        instead of killing an hour-scale build)."""
        def _do():
            _faults.faultpoint("build.chunk_read")
            return to_device(dataset[a:b])
        return _retry.retry_call(_do, site="build.chunk_read",
                                 policy=_retry.IO_POLICY)

    pq_dim = params.pq_dim or _default_pq_dim(dim)
    pq_len = -(-dim // pq_dim)
    rot_dim = pq_dim * pq_len
    K = 1 << params.pq_bits
    key = jax.random.PRNGKey(params.seed)

    km = KMeansBalancedParams(n_iters=params.kmeans_n_iters,
                              metric="cosine" if spherical else "l2",
                              seed=params.seed)
    if manifest is not None:
        # any manifest phase implies trained quantizers on disk (the
        # first manifest write happens after the train checkpoint);
        # loading raises a clear error when the state file is missing
        _say("resume: loading quantizer state from checkpoint")
        q = ck.load_arrays("quantizers")
        centers = jnp.asarray(q["centers"])
        rotation = jnp.asarray(q["rotation"])
        centers_rot = jnp.asarray(q["centers_rot"])
        codebooks = jnp.asarray(q["codebooks"])
    else:
        # 1. quantizers on a bounded random subsample (sorted: memmap
        # locality)
        n_train = min(n, max_train_rows,
                      max(params.n_lists * 4, 4 * K,
                          int(n * params.kmeans_trainset_fraction)))
        rng = np.random.default_rng(params.seed)
        tr_idx = np.sort(rng.choice(n, n_train, replace=False))
        _say(f"sampling {n_train} train rows")
        if hasattr(dataset, "sample_rows"):  # device-chunk provider
            trainset = to_device(dataset.sample_rows(tr_idx))
        else:
            trainset = _retry.retry_call(
                lambda: to_device(dataset[tr_idx]),
                site="build.train_sample", policy=_retry.IO_POLICY)
        _say("training quantizers (coarse kmeans + rotation + codebooks)")
        with span("train"):
            centers, rotation, centers_rot, codebooks = _train_quantizers(
                trainset, params, dim, pq_dim, pq_len, K, key, km)
            jax.block_until_ready(codebooks)
        del trainset
        if ck is not None:
            # kmeans centroid state + rotation + codebooks: the state a
            # resume must NOT retrain (f32 round-trips bit-exact, so a
            # resumed encode is identical to an uninterrupted one)
            ck.save_arrays("quantizers",
                           centers=np.asarray(centers),
                           rotation=np.asarray(rotation),
                           centers_rot=np.asarray(centers_rot),
                           codebooks=np.asarray(codebooks))
            ck.write_manifest({**base_manifest, "phase": "label"})
    _say("quantizers trained; label pass")

    # 2. streaming label pass → histogram → list capacity (loaded from
    # the checkpoint when the resume manifest says the pass completed)
    from raft_tpu.neighbors.ivf_flat import _fit_list_size

    from raft_tpu.core.interruptible import cancellation_point

    avg = max(1, n // params.n_lists)
    have_labels = (manifest is not None
                   and manifest.get("phase") in ("encode", "done"))
    if have_labels:
        _say("resume: loading label pass from checkpoint")
        labels = np.asarray(ck.load_arrays("labels")["labels"], np.int32)
        expects(labels.shape[0] == n,
                "resume label checkpoint holds %d rows, dataset has %d",
                labels.shape[0], n)
        L = int(manifest["L"])
        counts = np.bincount(labels[labels < params.n_lists],
                             minlength=params.n_lists)
    else:
        with span("label"):
            if params.spill:
                # top-2 labels, then cap+spill (see IndexParams.spill):
                # L is the cap itself, not the skewed max load
                from raft_tpu.neighbors import ivf_common as ic
                from raft_tpu.neighbors.ivf_flat import _lane_round

                NC = min(ic.SPILL_DEPTH, params.n_lists)
                lk = np.empty((n, NC), np.int32)
                for a in range(0, n, chunk_rows):
                    cancellation_point()
                    b = min(n, a + chunk_rows)
                    lk[a:b] = np.asarray(
                        kmeans_balanced.predict_topk(centers,
                                                     read_chunk(a, b),
                                                     NC, km))
                    if a % (8 * chunk_rows) == 0:
                        _say(f"labeled {b}/{n}")
                L = _lane_round(int(avg * params.list_size_cap_factor))
                _say("spilling assignments")
                labels = np.asarray(ic.spill_assignments(
                    jnp.asarray(lk[:, 0]), jnp.asarray(lk[:, 1]),
                    params.n_lists, L,
                    *[jnp.asarray(lk[:, c]) for c in range(2, lk.shape[1])]))
                del lk
                _say("spill done; encode pass")
                n_spill_drop = int((labels >= params.n_lists).sum())
                if n_spill_drop:
                    from raft_tpu.core import logging as _log
                    _log.warn("ivf_pq chunked build: %d rows overflowed both "
                              "choices at cap %d", n_spill_drop, L)
                counts = np.bincount(labels[labels < params.n_lists],
                                     minlength=params.n_lists)
            else:
                labels = np.empty(n, np.int32)
                for a in range(0, n, chunk_rows):
                    cancellation_point()  # chunk seams are cancellation points
                    b = min(n, a + chunk_rows)
                    labels[a:b] = np.asarray(
                        kmeans_balanced.predict(centers,
                                                read_chunk(a, b), km))
                counts = np.bincount(labels, minlength=params.n_lists)
                L = _fit_list_size(counts, avg, params.list_size_cap_factor)
        if ck is not None:
            ck.save_arrays("labels", labels=labels)
            ck.write_manifest({**base_manifest, "phase": "encode",
                               "L": int(L), "chunks_done": 0})
    nbytes = packed_nbytes(pq_dim, params.pq_bits)

    # 3. streaming encode + pack into the preallocated index
    def encode_range(lo, hi):
        """Encode dataset[lo:hi) → host (packed codes, norms). A chunk
        that hits RESOURCE_EXHAUSTED is halved and retried (each row's
        encode is independent, so splitting changes nothing but the
        peak working set) — the build entry point's degradation rung."""
        try:
            xb = read_chunk(lo, hi)
            lb = jnp.asarray(labels[lo:hi])
            codes, norms = _encode_with_norms(
                jnp.matmul(xb, rotation.T, precision=get_precision()),
                centers_rot,
                                              lb, codebooks,
                                              params.codebook_kind)
            return (pack_bits_np(np.asarray(codes), params.pq_bits),
                    np.asarray(norms))
        except Exception as e:
            if not _degrade.is_resource_exhausted(e) or hi - lo <= 1024:
                raise
            _degrade.note_step("ivf_pq.build_chunked", "chunk",
                               "half_chunk", "resource_exhausted")
            from raft_tpu.core import logging as _log

            _log.warn("ivf_pq chunked build: RESOURCE_EXHAUSTED encoding "
                      "rows [%d, %d) — halving the chunk", lo, hi)
            mid = (lo + hi) // 2
            c1, n1 = encode_range(lo, mid)
            c2, n2 = encode_range(mid, hi)
            return np.concatenate([c1, c2]), np.concatenate([n1, n2])

    chunks_done = int(manifest.get("chunks_done", 0)) if have_labels else 0
    packed = np.zeros((params.n_lists, L, nbytes), np.uint8)
    # global ids stamped below are a + row ∈ [0, n): the table width
    # follows the POLICY dtype of n (core.ids) — int64 past 2³¹ rows,
    # where the old hard np.int32 silently wrapped
    ids = np.full((params.n_lists, L), -1, _ids.np_id_dtype(n))
    pnorm = np.zeros((params.n_lists, L), np.float32)
    cursor = np.zeros(params.n_lists, np.int64)  # next free slot per list
    dropped = 0
    with span("encode_pack"):
        for ci, a in enumerate(range(0, n, chunk_rows)):
            b = min(n, a + chunk_rows)
            if ci < chunks_done:
                # completed before the interruption: replay the encoded
                # shard (no device work) so the pack below is identical
                shard = ck.load_shard(ci)
                codes_h = np.asarray(shard["codes"], np.uint8)
                norms_h = np.asarray(shard["norms"], np.float32)
                expects(codes_h.shape[0] == b - a,
                        "resume shard %d holds %d rows, expected %d — "
                        "corrupt checkpoint; refusing to resume",
                        ci, codes_h.shape[0], b - a)
                _count_resume("resume.chunks_replayed")
            else:
                cancellation_point()
                _faults.faultpoint("build.chunk_encode")
                codes_h, norms_h = encode_range(a, b)
                if ck is not None:
                    # shard first, then the manifest that records it —
                    # a death between the two re-encodes one chunk, it
                    # never trusts a missing shard
                    ck.save_shard(ci, codes=codes_h, norms=norms_h)
                    ck.write_manifest({**base_manifest, "phase": "encode",
                                       "L": int(L), "chunks_done": ci + 1})
            lb_h = labels[a:b]
            order, sorted_l, slot = _stable_slots(lb_h, params.n_lists,
                                                  cursor)
            keep = (slot < L) & (sorted_l < params.n_lists)
            dropped += int((~keep).sum())
            rows = order[keep]
            ls, sl = sorted_l[keep], slot[keep].astype(np.int64)
            packed[ls, sl] = codes_h[rows]
            ids[ls, sl] = (a + rows).astype(ids.dtype)
            pnorm[ls, sl] = norms_h[rows]
            cursor = np.minimum(
                cursor + np.bincount(lb_h, minlength=params.n_lists)[
                    :params.n_lists], L)
            if a % (8 * chunk_rows) == 0:
                _say(f"encoded {b}/{n}")
    if ck is not None:
        ck.write_manifest({**base_manifest, "phase": "done", "L": int(L),
                           "chunks_done": -(-n // chunk_rows)})
    if dropped:
        from raft_tpu.core import logging as _log
        from raft_tpu.neighbors import ivf_common as ic

        _log.warn("ivf_pq chunked build: dropped %d overflow vectors", dropped)
        # the spill-marker rows are among them: keep excludes both
        ic.note_rows_dropped("ivf_pq", dropped)

    fold = (nbytes < 128 and packed.nbytes > (1 << 30)
            and (L * nbytes) % 128 == 0)
    if fold:  # lane-fold big code arrays (see IvfPqIndex.codes_folded)
        packed = packed.reshape(params.n_lists, -1, 128)
    index = IvfPqIndex(
        centers=centers, centers_rot=centers_rot, rotation=rotation,
        codebooks=codebooks, packed_codes=ser.to_device_chunked(packed),
        packed_ids=jnp.asarray(ids), packed_norms=jnp.asarray(pnorm),
        list_sizes=jnp.asarray(np.minimum(counts, L).astype(np.int32)),
        metric=mt.value, codebook_kind=params.codebook_kind,
        pq_bits=params.pq_bits, pq_dim_static=pq_dim, codes_folded=fold)
    if _want_recon_cache(params, params.n_lists, L, rot_dim):
        index = index.replace(packed_recon=_build_recon_cache(index))
    _istats.note_index_stats(index, name="ivf_pq.build_chunked",
                             cheap=True)
    return index


@traced("raft_tpu.ivf_pq.build_distributed")
def build_distributed(dataset, params: Optional[IndexParams] = None, *,
                      mesh, axis: str = "shard",
                      chunk_rows: int = 1 << 18,
                      max_train_rows: int = 1 << 21,
                      prefetch: bool = True,
                      coarse: str = "replicated",
                      checkpoint_dir: Optional[str] = None,
                      resume=False, progress: bool = False):
    """Distributed billion-scale build from a host array/memmap — the
    pod twin of :func:`build_chunked` (reference: the raft-dask MNMG
    build lane, SURVEY §2.15; ROADMAP item 2's SIFT-1B path). Returns a
    :class:`raft_tpu.parallel.ivf.ShardedIvfPq` that the PR-8 sharded
    searcher (``search``'s ``mesh=`` dispatch, ring merge and fused
    scan-in-ring included) consumes directly.

    Structure (details: :mod:`raft_tpu.parallel.build`):

    - quantizers trained ONCE from a cross-shard trainset gathered with
      one ``allgatherv`` — by default (``coarse="replicated"``) the
      exact single-host trainer over the exact single-host sample, so
      ``parallel.build.assemble_ivf_pq`` of the result is
      **bit-identical** to ``build_chunked`` over the same
      dataset/params; ``coarse="distributed"`` swaps in the psum-Lloyd
      MNMG trainer (:func:`raft_tpu.cluster.distributed.fit`) when the
      trainset itself is too big to replicate (parity waived);
    - each shard walks only its contiguous slice of ``dataset`` in
      ``chunk_rows`` chunks through a double-buffered host→HBM
      prefetcher (chunk N+1's read + ``device_put`` hide under chunk
      N's encode; ``build.prefetch.{hit,stall}`` counters and the
      ``span.*.encode`` / ``span.*.h2d`` rows prove the overlap;
      ``prefetch=False`` keeps the serialized copy-then-encode walk for
      comparison). Reads retry under the PR-7 IO policy;
    - the only post-train collective is one ``allgatherv`` of per-list
      counts — encoded codes/ids/norms never cross the interconnect;
    - ``checkpoint_dir=`` makes the pod build preemption-safe per
      shard: per-(shard, chunk) encoded shards + a shard-axis manifest,
      resume replays to a sha-identical sharded index (fingerprints
      computed once, validated on resume — same refusal matrix as
      ``build_chunked``)."""
    if params is None:
        params = IndexParams()
    from raft_tpu.parallel import build as _dbuild

    return _dbuild.build_ivf_pq_distributed(
        dataset, params, mesh, axis=axis, chunk_rows=chunk_rows,
        max_train_rows=max_train_rows, prefetch=prefetch, coarse=coarse,
        checkpoint_dir=checkpoint_dir, resume=resume, progress=progress)


def _want_recon_cache(params: IndexParams, n_lists: int, L: int,
                      rot_dim: int) -> bool:
    if params.cache_reconstruction == "never":
        return False
    if params.cache_reconstruction == "always":
        return True
    # "auto": cap at ~1/5 of the local device's memory (3 GB on a 16 GB
    # chip — covers 1M×128 f32-equivalent datasets with room for codes,
    # queries and accumulators). The scan reads the cache instead of
    # decoding codes per probe, and the fast scalar-prefetch kernel
    # requires it; devices that don't report memory get the 16 GB-class
    # default.
    from raft_tpu.obs import hbm as _hbm

    cap = 3 << 30
    limit = _hbm.bytes_limit()
    if limit:
        cap = min(cap, limit // 5)
    return n_lists * L * rot_dim * 2 <= cap


@jax.jit
def _build_recon_cache(index: IvfPqIndex) -> jax.Array:
    """bf16 reconstruction (c + decoded residual) of every packed slot.

    The decode is blocked over list chunks with ``lax.map`` (mirroring
    _encode_rows' 4096-row blocking): a single unblocked decode would
    materialize a one-hot K× the code volume if XLA fails to fuse it —
    near the 1 GB "auto" cache cap that is a multi-GB peak."""
    from raft_tpu.neighbors import ivf_common as ic

    n_lists, L = index.packed_ids.shape
    nb = packed_nbytes(index.pq_dim, index.pq_bits)
    S = index.pq_dim
    chunk = ic.choose_list_chunk(n_lists, max(1, -(-4096 // max(L, 1))))
    n_chunks = n_lists // chunk
    per_cluster = index.codebook_kind == "per_cluster"

    def decode_chunk(args):
        if per_cluster:
            codes_p, crot, cb = args
            dec = _decode_lists_cluster(index.unpack_codes(codes_p), cb)
        else:
            codes_p, crot = args
            codes = index.unpack_codes(codes_p)
            dec = _decode_codes(codes.reshape(chunk * L, S),
                                index.codebooks).reshape(chunk, L, -1)
        return (dec + crot[:, None, :]).astype(jnp.bfloat16)

    # row-major reshape is layout-agnostic: folded storage unfolds here
    ins = (index.packed_codes.reshape(n_chunks, chunk, L, nb),
           index.centers_rot.reshape(n_chunks, chunk, -1))
    if per_cluster:
        K, P = index.codebooks.shape[1:]
        ins = ins + (index.codebooks.reshape(n_chunks, chunk, K, P),)
    out = lax.map(decode_chunk, ins)
    return out.reshape(n_lists, L, -1)


@traced("raft_tpu.ivf_pq.extend")
def extend(index: IvfPqIndex, new_vectors: jax.Array,  # graftlint: disable-fn=GL01 (host re-pack by design)
           new_ids: Optional[jax.Array] = None) -> IvfPqIndex:
    """Append vectors (reference: ivf_pq::extend): encode against existing
    centers/codebooks, host re-pack with capacity growth."""
    mt = resolve_metric(index.metric)
    spherical = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)
    km = KMeansBalancedParams(metric="cosine" if spherical else "l2")
    x = jnp.asarray(new_vectors, jnp.float32)
    if mt == DistanceType.CosineExpanded:
        x = x / jnp.sqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-12))
    old_n = index.size
    if new_ids is None:
        new_ids = _ids.make_ids(x.shape[0], start=old_n)

    labels = kmeans_balanced.predict(index.centers, x, km)
    codes, norms = _encode_with_norms(
        jnp.matmul(x, index.rotation.T, precision=get_precision()),
        index.centers_rot,
                                      labels, index.codebooks,
                                      index.codebook_kind)

    n_lists, L = index.packed_ids.shape
    S = packed_nbytes(index.pq_dim, index.pq_bits)  # bytes per code row
    old_sizes = np.asarray(index.list_sizes)
    labels_h = np.asarray(labels)
    need = old_sizes + np.bincount(labels_h, minlength=n_lists)
    new_L = max(L, max(8, -(-int(need.max()) // 8) * 8))

    old_ids = np.asarray(index.packed_ids)
    nid_h0 = np.asarray(new_ids)
    packed = np.zeros((n_lists, new_L, S), np.uint8)
    ids = np.full((n_lists, new_L), -1,
                  _ids.np_id_dtype_like(old_ids, nid_h0))
    pnorm = np.zeros((n_lists, new_L), np.float32)
    packed[:, :L] = np.asarray(index.packed_codes).reshape(n_lists, L, -1)
    ids[:, :L] = old_ids
    pnorm[:, :L] = np.asarray(index.packed_norms)
    codes_h = pack_bits_np(np.asarray(codes), index.pq_bits)
    norms_h, nid_h = np.asarray(norms), nid_h0
    # vectorized append: slot = old_size[list] + rank within the new rows
    order, sorted_l, slot = _stable_slots(labels_h, n_lists, old_sizes)
    keep = slot < new_L
    rows = order[keep]
    ls, sl = sorted_l[keep], slot[keep]
    packed[ls, sl] = codes_h[rows]
    ids[ls, sl] = nid_h[rows]
    pnorm[ls, sl] = norms_h[rows]
    fill = np.minimum(need, new_L)
    out = IvfPqIndex(
        centers=index.centers, centers_rot=index.centers_rot,
        rotation=index.rotation, codebooks=index.codebooks,
        packed_codes=jnp.asarray(
            packed.reshape(n_lists, -1, 128)
            if index.codes_folded and (new_L * S) % 128 == 0 else packed),
        packed_ids=jnp.asarray(ids),
        packed_norms=jnp.asarray(pnorm),
        list_sizes=jnp.asarray(fill.astype(np.int32)), metric=index.metric,
        codebook_kind=index.codebook_kind, pq_bits=index.pq_bits,
        pq_dim_static=index.pq_dim,
        codes_folded=index.codes_folded and (new_L * S) % 128 == 0)
    if index.packed_recon is not None:
        out = out.replace(packed_recon=_build_recon_cache(out))
    _istats.note_index_stats(out, name="ivf_pq.extend", cheap=True)
    return out


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

# Stage scopes of the search programs (op_name metadata only): the whole
# program is the scan of the probed lists with its merge; probe
# selection, and the query's rotation and lookup table where the
# program builds one outside the scan kernel, are stages inside it.
_COARSE = "raft_tpu.ivf_pq.coarse_quantize"
_LUT = "raft_tpu.ivf_pq.lut"
_SCAN = "raft_tpu.ivf_pq.scan"


def _qd_from_qlut(idx: jax.Array, qlut: jax.Array) -> jax.Array:
    """⟨q,d⟩ per candidate from the query-only LUT: ``idx`` [t, C, S]
    i32 code values, ``qlut`` [t, S, K] → [t, C] f32. One-hot MXU
    contraction on TPU (per-lane gathers are the slowest op there; the
    iota-compare one-hot fuses into the matmul's operand feed — the TPU
    counterpart of the reference's fused LUT scan,
    ivf_pq_compute_similarity-inl.cuh); CPU keeps the natural gather
    (its XLA doesn't fuse the one-hot and would materialize it)."""
    if jax.default_backend() != "cpu":
        onehot = jax.nn.one_hot(idx, qlut.shape[-1], dtype=jnp.float32)
        return jnp.einsum("tcsk,tsk->tc", onehot, qlut,
                          precision=get_precision(),
                          preferred_element_type=jnp.float32)
    idx_t = jnp.transpose(idx, (0, 2, 1))                       # [t, S, C]
    gath = jnp.take_along_axis(qlut.astype(jnp.float32), idx_t, axis=2)
    return jnp.sum(gath, axis=1)                                # [t, C]


def _finish_candidates(dots, cand_ids, cand_norms, q_sq, mt, k,
                       filter_bits=None):
    """Shared candidate epilogue: ``dots`` = ⟨q, c+d⟩ per candidate (from
    the LUT decomposition or the recon gather) → metric distances, mask,
    select, id gather, cosine flip. Used by both the per_query path and
    the Pallas LUT tier, so their results cannot drift."""
    ip_like = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)
    if ip_like:
        dists = dots
        invalid = -jnp.inf
        final_min = False
    else:
        dists = jnp.maximum(q_sq[:, None] - 2.0 * dots + cand_norms, 0.0)
        if mt == DistanceType.L2SqrtExpanded:
            dists = jnp.sqrt(dists)
        invalid = jnp.inf
        final_min = True
    valid = cand_ids >= 0
    if filter_bits is not None:
        from raft_tpu.neighbors.sample_filter import passes

        valid = passes(filter_bits, cand_ids)
    dists = jnp.where(valid, dists, invalid)
    vals, pos = _select_k(dists, k, select_min=final_min)
    ids = jnp.take_along_axis(cand_ids, pos, axis=1)
    if ip_like and mt == DistanceType.CosineExpanded:
        vals = 1.0 - vals  # report cosine distance
    return vals, ids


def _coarse_probes(index: IvfPqIndex, q_all: jax.Array, n_probes: int,
                   ip_like: bool):
    """Coarse probe selection on q·c (reference: select_clusters,
    ivf_pq_search.cuh:70-156) — plain helper traced inside both jitted
    search paths (per_query and grouped), so the metric-dependent
    expansion lives in ONE place. Returns (qc [m, n_lists], probes
    [m, n_probes])."""
    qc = lax.dot_general(q_all, index.centers, (((1,), (1,)), ((), ())),
                         precision=get_precision(),
                         preferred_element_type=jnp.float32)
    if ip_like:
        _, probes = _select_k(qc, n_probes, select_min=False)
    else:
        c_sq = jnp.sum(index.centers**2, axis=1)
        _, probes = _select_k(c_sq[None, :] - 2.0 * qc, n_probes,
                              select_min=True)
    return qc, probes


@partial(jax.jit, static_argnames=("k", "n_probes", "query_tile",
                                   "lut_dtype"))
@annotate(_SCAN)
def _search_impl(index: IvfPqIndex, queries: jax.Array, k: int,
                 n_probes: int, query_tile: int, filter_bits=None,
                 lut_dtype: str = "float32"):
    mt = resolve_metric(index.metric)
    q_all = jnp.asarray(queries, jnp.float32)
    if mt == DistanceType.CosineExpanded:
        q_all = q_all / jnp.sqrt(jnp.maximum(
            jnp.sum(q_all * q_all, -1, keepdims=True), 1e-12))
    m = q_all.shape[0]
    S, K, P = index.pq_dim, index.pq_book_size, index.pq_len
    per_cluster = index.codebook_kind == "per_cluster"
    L = index.max_list_size
    ip_like = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)

    # qc itself is needed regardless — the ⟨q,c⟩ term of the decomposition
    with annotate(_COARSE):
        qc, probes = _coarse_probes(index, q_all, n_probes, ip_like)
        qc_probed_all = jnp.take_along_axis(qc, probes, axis=1)  # [m, P]

    with annotate(_LUT):
        q_rot_all = jnp.matmul(q_all, index.rotation.T,
                               precision=get_precision())
        q_sq_all = jnp.sum(q_rot_all * q_rot_all, axis=1)

    # recon-dot preempts the LUT scan only when (a) the cache exists,
    # (b) the user didn't ask for LUT quantization, and (c) the one-hot
    # operand feed [C, S, K] is large enough to be dangerous — observed
    # device fault at C≈254k, S=64, K=256 (n=1M, L≈4k); small indexes
    # keep the exact f32-LUT ADC so per_query results are unchanged
    use_recon_dot = (index.packed_recon is not None
                     and lut_dtype == "float32"
                     and n_probes * L * S * K >= (1 << 28))

    def search_tile(args):
        q_rot, probe, qc_probed, q_sq = args
        t = q_rot.shape[0]
        q_sub = q_rot.reshape(t, S, P)
        cand_ids = index.packed_ids[probe].reshape(t, n_probes * L)
        cand_norms = index.packed_norms[probe].reshape(t, n_probes * L)
        if use_recon_dot:
            # one contraction against the gathered bf16 reconstructions:
            # ⟨q_rot, c+d⟩ = ⟨q,c⟩ + ⟨q,d⟩, so the LUT decomposition
            # collapses and no one-hot is formed
            rows = index.packed_recon[probe].reshape(t, n_probes * L, -1)
            dots = jnp.einsum("td,tcd->tc", q_rot,
                              rows.astype(jnp.float32),
                              precision=get_precision(),
                              preferred_element_type=jnp.float32)
            return finish_tile(dots, cand_ids, cand_norms, q_sq)
        codes_p = index.codes_chunk(probe.reshape(-1)).reshape(
            t, n_probes, L, -1)                           # [t, Pr, L, nb]
        codes = index.unpack_codes(codes_p)               # [t, Pr, L, S]
        # ⟨q, d⟩: qd[t,c] = Σ_s qlut[t, s, codes[t,c,s]].  On TPU this is
        # formulated as a one-hot contraction: per-lane dynamic gathers
        # are the slowest op on a TPU, while the iota-compare one-hot
        # fuses into the MXU matmul's operand feed (never hits HBM) —
        # the TPU counterpart of the reference's fused LUT scan
        # (ivf_pq_compute_similarity-inl.cuh).  CPU keeps the gather
        # (its XLA doesn't fuse the one-hot and would materialize it).
        if per_cluster:
            # LUT is per (query, probed cluster): ⟨q_s, cb[probe][k]⟩
            cb_probed = index.codebooks[probe]            # [t, Pr, K, P]
            with annotate(_LUT):
                lut = jnp.einsum("tsp,tjkp->tjsk", q_sub, cb_probed,
                                 precision=get_precision())  # [t, Pr, S, K]
                lut = _quantize_lut(lut, lut_dtype)
            if jax.default_backend() == "cpu":
                # CPU XLA won't fuse the 5-D one-hot — gather instead
                codes_t = jnp.transpose(codes, (0, 1, 3, 2))  # [t, Pr, S, L]
                gath = jnp.take_along_axis(
                    lut.astype(jnp.float32), codes_t.astype(jnp.int32),
                    axis=3)                               # [t, Pr, S, L]
                qd = jnp.sum(gath, axis=2).reshape(t, n_probes * L)
            else:
                oh = jax.nn.one_hot(codes.astype(jnp.int32), K,
                                    dtype=jnp.float32)    # [t, Pr, L, S, K]
                qd = jnp.einsum("tjlsk,tjsk->tjl", oh, lut,
                                precision=get_precision(),
                                preferred_element_type=jnp.float32
                                ).reshape(t, n_probes * L)
        else:
            # query-only LUT: ⟨q_s, cb[s,k]⟩ — one batched MXU contraction
            with annotate(_LUT):
                qlut = jnp.einsum("tsp,skp->tsk", q_sub, index.codebooks,
                                  precision=get_precision())  # [t, S, K]
                qlut = _quantize_lut(qlut, lut_dtype)
            idx = codes.reshape(t, n_probes * L, S).astype(jnp.int32)
            qd = _qd_from_qlut(idx, qlut)
        qcand = jnp.broadcast_to(qc_probed[:, :, None],
                                 (t, n_probes, L)).reshape(t, n_probes * L)
        return finish_tile(qcand + qd, cand_ids, cand_norms, q_sq)

    def finish_tile(dots, cand_ids, cand_norms, q_sq):
        return _finish_candidates(dots, cand_ids, cand_norms, q_sq, mt, k,
                                  filter_bits=filter_bits)

    if m <= query_tile:
        return search_tile((q_rot_all, probes, qc_probed_all, q_sq_all))

    n_tiles = -(-m // query_tile)
    pad = n_tiles * query_tile - m
    qr = jnp.pad(q_rot_all, ((0, pad), (0, 0)))
    pr = jnp.pad(probes, ((0, pad), (0, 0)))
    qp = jnp.pad(qc_probed_all, ((0, pad), (0, 0)))
    qs = jnp.pad(q_sq_all, (0, pad))
    vals, ids = lax.map(search_tile, (
        qr.reshape(n_tiles, query_tile, -1),
        pr.reshape(n_tiles, query_tile, -1),
        qp.reshape(n_tiles, query_tile, -1),
        qs.reshape(n_tiles, query_tile)))
    return (vals.reshape(-1, k)[:m], ids.reshape(-1, k)[:m])


@partial(jax.jit, static_argnames=("k", "n_probes", "seg", "n_seg",
                                   "seg_chunk", "use_pallas", "select_impl",
                                   "select_recall", "use_segk"))
@annotate(_SCAN)
def _search_grouped(index: IvfPqIndex, queries: jax.Array, k: int,
                    n_probes: int, seg: int, n_seg: int, seg_chunk: int,
                    use_pallas: bool = False, filter_bits=None,
                    select_impl: str = "exact",
                    select_recall: float = 0.95,
                    use_segk: bool = False):
    """Segmented list-centric batch scan (see ivf_common): each probed
    list's codes are decoded once per owned segment (one-hot MXU
    contraction — or skipped entirely when the bf16 reconstruction cache
    is present) and scanned against that segment's queries with one
    batched MXU contraction. Probe selection, segmenting, scan and merge
    are ONE jitted program, statically shaped by (B, n_probes, n_lists,
    seg). Counterpart of the reference's compute_similarity kernel
    (ivf_pq_compute_similarity-inl.cuh) with the loop order inverted:
    the reference re-reads packed codes per query, this reads them per
    query *segment*."""
    from raft_tpu.neighbors import ivf_common as ic

    mt = resolve_metric(index.metric)
    q_all = jnp.asarray(queries, jnp.float32)
    if mt == DistanceType.CosineExpanded:
        q_all = q_all / jnp.sqrt(jnp.maximum(
            jnp.sum(q_all * q_all, -1, keepdims=True), 1e-12))
    B = q_all.shape[0]
    n_lists, L = index.packed_ids.shape
    per_cluster = index.codebook_kind == "per_cluster"
    ip_like = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)
    sqrt_out = mt == DistanceType.L2SqrtExpanded
    select_min = not ip_like
    invalid = -jnp.inf if ip_like else jnp.inf

    from raft_tpu.ops import pallas_kernels as _pk

    use_pallas = use_pallas and index.packed_recon is not None

    with annotate(_COARSE):
        _, probes = _coarse_probes(index, q_all, n_probes, ip_like)
    seg_list, seg_q, pair_seg, pair_slot = ic.segment_probes(
        probes, n_lists, seg, n_seg)

    with annotate(_LUT):
        q_rot = jnp.matmul(q_all, index.rotation.T,
                           precision=get_precision())     # [B, rot_dim]
        q_sq = jnp.sum(q_rot * q_rot, axis=1)
    valid_full = index.packed_ids >= 0
    if filter_bits is not None:
        from raft_tpu.neighbors.sample_filter import passes

        valid_full &= passes(filter_bits, index.packed_ids)

    kk_ = min(k, L)
    if use_segk:
        # scalar-prefetch kernel over the bf16 recon cache (see ivf_flat:
        # the XLA gather of list blocks runs ~20 GB/s and dominates).
        # A filter rides as a SENTINEL-MASKED id table: filtered slots
        # become the -1 invalid id the kernel already poisons to +inf
        # before its bin pre-selection (the GL13 pattern), so the bins
        # hold only kept candidates — dispatch admits the [n_lists, L]
        # mask+i32 transient via filtered_scan_mem_ok(slot_bytes=5)
        met = "ip" if ip_like else "l2"
        qv_all = q_rot[jnp.clip(seg_q, 0, B - 1)]         # [n_seg, S, rot]
        seg_ids = (index.packed_ids if filter_bits is None
                   else jnp.where(valid_full, index.packed_ids, -1))
        keys, kids = _pk.segmented_scan_topk(
            seg_list, qv_all, index.packed_recon, seg_ids, met,
            interpret=not _pk._on_tpu())
        out_vals, out_ids = ic.merge_bin_results(
            keys, kids, pair_seg, pair_slot, k, select_min, invalid,
            select_recall)
        if sqrt_out:
            out_vals = jnp.sqrt(out_vals)
        if mt == DistanceType.CosineExpanded:
            out_vals = 1.0 - out_vals
        return out_vals, out_ids

    C = seg_chunk
    n_chunks = -(-n_seg // C)
    nsp = n_chunks * C
    seg_list = jnp.pad(seg_list, (0, nsp - n_seg))
    seg_q = jnp.pad(seg_q, ((0, nsp - n_seg), (0, 0)), constant_values=-1)
    has_recon = index.packed_recon is not None

    # billion-scale guard: a GATHER of list chunks from a multi-GB code
    # array inside the scan loop provokes XLA into rematerializing
    # pipelined SLAB COPIES of the whole array (measured: 3× 1.88 GB
    # temps at 100M — an instant compile OOM next to the resident
    # index). dynamic_slice at C=1 keeps the loop slab-free.
    slice_scan = index.packed_codes.nbytes > _SLICE_SCAN_BYTES
    if slice_scan:
        C = 1
        n_chunks = n_seg
        nsp = n_seg
        seg_list = seg_list[:n_seg]
        seg_q = seg_q[:n_seg]

    def _chunk(arr, sl):
        if slice_scan:
            return lax.dynamic_slice(
                arr, (sl[0],) + (0,) * (arr.ndim - 1),
                (1,) + arr.shape[1:])
        return arr[sl]

    def scan_chunk(args):
        sl, qt = args                                     # [C], [C, seg]
        norms = _chunk(index.packed_norms, sl)
        lids = _chunk(index.packed_ids, sl)
        valid = lids >= 0 if slice_scan else valid_full[sl]
        if slice_scan and filter_bits is not None:
            from raft_tpu.neighbors.sample_filter import passes

            valid &= passes(filter_bits, lids)
        if has_recon:
            recon = _chunk(index.packed_recon, sl)        # [C, L, rot]
        else:
            cp = _chunk(index.packed_codes, sl)
            if index.codes_folded:
                cp = cp.reshape(cp.shape[0], L, -1)
            codes = index.unpack_codes(cp)
            if per_cluster:
                decoded = _decode_lists_cluster(codes,
                                                _chunk(index.codebooks, sl))
            else:
                decoded = _decode_codes(codes, index.codebooks)
            recon = decoded + _chunk(index.centers_rot, sl)[:, None, :]
        qi = jnp.clip(qt, 0, B - 1)
        qv = q_rot[qi]                                    # [C, seg, rot]
        # pad slots (qt == -1) compute against query 0 and are simply
        # never gathered back
        if use_pallas:
            # fused contraction + epilogue + local top-k in VMEM over the
            # bf16 reconstructions (reference: compute_similarity's fused
            # block-sort top-k, ivf_pq_compute_similarity-inl.cuh:439);
            # the l2 epilogue recomputes ‖c+d‖² from the bf16 recon —
            # ~1e-3 relative drift vs the stored f32 norms
            met = "ip" if ip_like else "l2"
            mask_add = jnp.where(valid, 0.0, jnp.inf)
            keys, pos = _pk.grouped_scan_topk(
                qv, recon, mask_add, kk, met, bq=seg,
                interpret=not _pk._on_tpu())
            vals = -keys if ip_like else keys
            vals = jnp.where(pos < 0, invalid, vals)
            cids = jax.vmap(lambda l, p: l[jnp.clip(p, 0, L - 1)])(lids, pos)
            cids = jnp.where(pos < 0, -1, cids)
            return vals, cids
        scores = jnp.einsum("gqd,gld->gql", qv,
                            recon.astype(jnp.float32),
                            precision=get_precision(),
                            preferred_element_type=jnp.float32)
        if ip_like:
            dists = scores
        else:
            dists = jnp.maximum(
                q_sq[qi][:, :, None] + norms[:, None, :] - 2.0 * scores, 0.0)
        dists = jnp.where(valid[:, None, :], dists, invalid)
        if select_impl == "approx":
            # hardware top-k (TPU approx reduction) — see ivf_flat
            if select_min:
                vals, pos = lax.approx_min_k(
                    dists.reshape(C * seg, L), kk,
                    recall_target=select_recall)
            else:
                vals, pos = lax.approx_max_k(
                    dists.reshape(C * seg, L), kk,
                    recall_target=select_recall)
        else:
            vals, pos = _select_k(dists.reshape(C * seg, L), kk,
                                  select_min=select_min)
        vals = vals.reshape(C, seg, kk)
        pos = pos.reshape(C, seg, kk)
        cids = jax.vmap(lambda l, p: l[p])(lids, pos)
        cids = jnp.where(vals == invalid, -1, cids)
        return vals, cids

    kk = min(k, L)  # a single list holds at most L candidates
    vals, cids = lax.map(
        scan_chunk, (seg_list.reshape(n_chunks, C),
                     seg_q.reshape(n_chunks, C, seg)))
    vals = vals.reshape(nsp, seg, kk)
    cids = cids.reshape(nsp, seg, kk)

    pv, pi = ic.gather_segment_results(vals, cids, pair_seg, pair_slot)
    out_vals, out_ids = _select_k(pv.reshape(B, n_probes * kk),
                                  min(k, n_probes * kk),
                                  select_min=select_min,
                                  input_indices=pi.reshape(B, n_probes * kk))
    if k > n_probes * kk:
        pad = k - n_probes * kk
        out_vals = jnp.pad(out_vals, ((0, 0), (0, pad)),
                           constant_values=invalid)
        out_ids = jnp.pad(out_ids, ((0, 0), (0, pad)), constant_values=-1)
    if sqrt_out:
        out_vals = jnp.sqrt(out_vals)
    if mt == DistanceType.CosineExpanded:
        out_vals = 1.0 - out_vals
    return out_vals, out_ids


@partial(jax.jit, static_argnames=("k", "n_probes", "seg", "n_seg",
                                   "lut_dtype"))
@annotate(_SCAN)
def _search_lut_pallas(index: IvfPqIndex, queries: jax.Array, k: int,
                       n_probes: int, seg: int, n_seg: int,
                       filter_bits=None, lut_dtype: str = "float32"):
    """The ``scan_select="pallas"`` tier: segmented scan through the fused
    Pallas LUT kernel (ops.pallas_kernels.ivfpq_lut_scan_topk). Packed
    codes stream HBM→VMEM per segment, unpack/decode/accumulate/select
    happen on-chip, and only the [n_seg, seg, 256] bin tables come back —
    neither the decoded-f32 lists, the one-hot operands, nor the
    [B, n_probes·L] candidate tables ever exist in HBM. The merged bins
    run through the shared :func:`_finish_candidates` epilogue, so
    results cannot drift from the per_query path's semantics.

    ``filter_bits`` streams INTO the kernel as a per-candidate packed
    mask (``sample_filter.list_filter_bytes`` over the same id table
    the kernel scans, 1 bit/candidate): filtered candidates take the
    +inf/-1 sentinel BEFORE the 2×128-bin pre-selection, so the emitted
    bins hold only kept candidates and a selective filter no longer
    makes kept neighbors unreachable. The shared
    :func:`_finish_candidates` epilogue re-applies the same filter over
    the merged candidates — a no-op on the kernel's output, kept so the
    fused and unfused paths share one exclusion site."""
    from raft_tpu.neighbors import ivf_common as ic
    from raft_tpu.ops import pallas_kernels as _pk

    mt = resolve_metric(index.metric)
    q_all = jnp.asarray(queries, jnp.float32)
    if mt == DistanceType.CosineExpanded:
        q_all = q_all / jnp.sqrt(jnp.maximum(
            jnp.sum(q_all * q_all, -1, keepdims=True), 1e-12))
    B = q_all.shape[0]
    ip_like = mt in (DistanceType.InnerProduct, DistanceType.CosineExpanded)

    with annotate(_COARSE):
        _, probes = _coarse_probes(index, q_all, n_probes, ip_like)
    seg_list, seg_q, pair_seg, pair_slot = ic.segment_probes(
        probes, index.n_lists, seg, n_seg)
    with annotate(_LUT):
        q_rot = jnp.matmul(q_all, index.rotation.T,
                           precision=get_precision())
        q_sq = jnp.sum(q_rot * q_rot, axis=1)
    qv_all = q_rot[jnp.clip(seg_q, 0, B - 1)]         # [n_seg, seg, rot]

    filter_bytes = None
    if filter_bits is not None:
        from raft_tpu.neighbors import sample_filter as _sf

        # per-list packed keep bits over the SAME [n_lists, L] id table
        # the kernel streams — one gather + byte re-pack, n/8 bytes
        filter_bytes = _sf.list_filter_bytes(filter_bits,
                                             index.packed_ids)
    keys, kids = _pk.ivfpq_lut_scan_topk(
        seg_list, qv_all, index.packed_codes, index.packed_ids,
        index.packed_norms, index.centers_rot, index.codebooks,
        "ip" if ip_like else "l2", pq_bits=index.pq_bits,
        pq_dim=index.pq_dim, L=index.max_list_size, lut_dtype=lut_dtype,
        filter_bytes=filter_bytes, interpret=not _pk._on_tpu())
    pv, pi = ic.gather_segment_results(keys, kids, pair_seg, pair_slot)
    C = n_probes * keys.shape[-1]
    pv = pv.reshape(B, C)
    pi = pi.reshape(B, C)
    # the kernel emits minimized keys (l2: ‖c+d‖² − 2⟨q,c+d⟩; ip:
    # −⟨q,c+d⟩); recover the shared epilogue's ⟨q,c+d⟩ convention with
    # zero cand_norms so _finish_candidates reconstructs the metric
    dots = -pv if ip_like else -0.5 * pv
    kq = min(k, C)
    out_vals, out_ids = _finish_candidates(
        dots, pi, jnp.zeros_like(pv), q_sq, mt, kq,
        filter_bits=filter_bits)
    if k > kq:
        invalid = -jnp.inf if ip_like else jnp.inf
        if mt == DistanceType.CosineExpanded:
            invalid = jnp.inf  # reported as cosine distance
        out_vals = jnp.pad(out_vals, ((0, 0), (0, k - kq)),
                           constant_values=invalid)
        out_ids = jnp.pad(out_ids, ((0, 0), (0, k - kq)),
                          constant_values=-1)
    return out_vals, out_ids


def _count_scan_dispatch(impl: str, filtered: bool = False) -> None:
    """Record which scan engine ``search`` dispatched to (the obs
    ``ivf_pq.scan.dispatch{impl=...}`` counter) — eager, so it counts
    dispatch decisions, not device executions. Filtered searches carry
    a ``filtered=1`` label so "did the filtered workload stay on the
    fast tier?" is one counter query (the CI obs-smoke step asserts
    exactly this)."""
    if filtered:
        _obs_spans.count_dispatch("ivf_pq.scan", impl, filtered="1")
    else:
        _obs_spans.count_dispatch("ivf_pq.scan", impl)


def _count_lut_fallback(reason: str) -> None:
    """Record WHY a search eligible for (or explicitly requesting) the
    fused Pallas LUT tier ran elsewhere — the obs
    ``ivf_pq.scan.fallback{reason=...}`` counter. The dispatch counter
    alone shows only the engine that won; triage of "why isn't the
    oversampled config on the fast tier?" needs the losing reason:
    ``bin_capacity`` (n_probes·256 < k), ``per_cluster`` codebooks,
    ``mem_guard`` (lut_scan_mem_ok / filtered_scan_mem_ok declined), or
    ``kernel_ineligible`` (packed layout / VMEM / not on TPU). The
    ``filter_bitset`` reason is RETIRED: the kernels stream the bitset
    as a per-candidate mask, so a filter no longer disqualifies the
    tier (CI asserts the retired reason stays at zero)."""
    _obs_spans.count_fallback("ivf_pq.scan", reason)


def _route_refined(index: IvfPqIndex, queries: jax.Array, k: int,
                   params: "SearchParams", filter_bitset, dataset
                   ) -> Tuple[jax.Array, jax.Array]:
    """The ``refine="f32_regen"`` path: oversampled scan (k·refine_ratio
    candidates through whatever scan tier ``search`` picks), then an
    exact re-rank routed by what ``dataset`` is — the device refine
    dispatch tier (fused gather-refine kernel / XLA einsum), the
    device-chunk provider regen, or the host gather (reference:
    refine-inl.cuh's refinement_rate; deep-100m's headline rows)."""
    from raft_tpu.neighbors import refine as _refine

    expects(params.refine == "f32_regen",
            "unknown refine mode %r (supported: 'none', 'f32_regen')",
            params.refine)
    expects(dataset is not None,
            "refine='f32_regen' needs search(..., dataset=...): the "
            "exact rows to re-rank against")
    dshape = getattr(dataset, "shape", None)
    expects(dshape is not None and len(dshape) == 2
            and dshape[1] == index.dim,
            "refine dataset shape %s does not match the index dim %d",
            tuple(dshape) if dshape else None, index.dim)
    expects(params.refine_ratio >= 1.0,
            "refine_ratio must be >= 1 (got %s)", params.refine_ratio)
    k_cand = max(k, int(round(k * params.refine_ratio)))
    scan_params = dataclasses.replace(params, refine="none")
    # host-resident base → the memory tier (ISSUE 17): decide BEFORE
    # the scan — the tiered pipeline runs its own sub-batch scans so
    # each batch's candidate-row fetch can overlap the next scan
    if (not isinstance(dataset, jax.Array)
            and not hasattr(dataset, "_block")):
        from raft_tpu.neighbors import tiered as _tiered

        if _tiered.tiered_refine_wanted(dataset, queries.shape[0],
                                        k_cand, index.dim, params):
            return _tiered.search_refined_tiered(
                search, index, queries, k, k_cand, scan_params,
                filter_bitset, dataset, index.metric)
    _, i0 = search(index, queries, k_cand, scan_params, filter_bitset)
    if hasattr(dataset, "_block") and hasattr(dataset, "chunk_rows"):
        return _refine.refine_provider(dataset, queries, i0, k,
                                       metric=index.metric)
    if isinstance(dataset, jax.Array):
        # the scan already excluded filtered candidates from i0; the
        # refine-tier filter is defense in depth at zero extra traffic
        # (the fused kernel folds the bit test into its row-DMA queue)
        return _refine.refine(dataset, queries, i0, k, metric=index.metric,
                              filter_bits=filter_bitset)
    # host array / memmap, tiered declined or pinned "serial": the
    # serialized candidate-row gather
    return _refine.refine_gathered(dataset, queries, i0, k,
                                   metric=index.metric)


_lut_fallback_warned = False

# human-readable detail per fallback-counter reason. filter_bitset is
# NOT here: the fused tiers stream the bitset as a per-candidate mask
# now, so a filter no longer disqualifies the tier and warning for it
# would point at a cause that cannot occur.
_LUT_FALLBACK_DETAIL = {
    "bin_capacity": "too few probes for the requested k "
                    "(needs n_probes·256 ≥ k)",
    "per_cluster": "per_cluster codebooks (the kernel decodes "
                   "per_subspace only)",
    "mem_guard": "the lut_scan_mem_ok/filtered_scan_mem_ok HBM guard "
                 "declined the shape",
    "kernel_ineligible": "unsupported packed layout, VMEM budget, or "
                         "not on TPU",
}


def _warn_lut_fallback(reason: str) -> None:
    """Once-per-process notice that an explicit scan_select="pallas" was
    downgraded, carrying the CONCRETE reason the tier lost (the same
    label the ``ivf_pq.scan.fallback{reason=...}`` counter records) and
    the env override that forces the tier off-TPU."""
    global _lut_fallback_warned
    if _lut_fallback_warned:
        return
    _lut_fallback_warned = True
    from raft_tpu.core import logging as _log
    detail = _LUT_FALLBACK_DETAIL.get(reason, reason)
    _log.warn("ivf_pq: scan_select='pallas' requested but the fused LUT "
              "kernel cannot serve this search — reason=%s: %s "
              "(RAFT_TPU_PALLAS_LUTSCAN=always forces the tier off-TPU; "
              "the obs counter ivf_pq.scan.fallback{reason=%s} records "
              "every decline) — falling back to scan_select='approx'",
              reason, detail, reason)


@traced("raft_tpu.ivf_pq.search")
def search(index, queries: jax.Array, k: int,
           params: Optional[SearchParams] = None,
           filter_bitset: Optional[jax.Array] = None,
           dataset=None, *, mesh=None,
           mesh_axis: str = "shard",
           merge: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Search (reference: ivf_pq::search, ivf_pq-inl.cuh:478; filtered
    overload search_with_filtering). Distances are PQ-approximate (as the
    reference's) unless ``params.refine="f32_regen"``, which scans
    ``k·refine_ratio`` candidates and re-ranks them exactly against
    ``dataset`` (device array → the fused gather-refine tier on TPU
    oversampled shapes; host array/memmap → host gather; device-chunk
    provider → on-device regen). Standalone re-ranking stays available
    as neighbors.refine.
    ``filter_bitset``: optional packed bitset over dataset rows (see
    neighbors.sample_filter) — cleared bits are excluded.

    **Pod-scale dispatch**: handed a ``parallel.ShardedIvfPq`` (plus its
    ``mesh``), the same entry routes to the sharded search tier —
    per-shard scan (+ per-shard fused refine when
    ``params.refine="f32_regen"`` and ``dataset`` is given) and the
    cross-shard merge tier picked by ``merge`` (auto | allgather |
    ring, see ``parallel.merge``). Filter bitsets are single-chip-only
    for now."""
    if params is None:
        params = SearchParams()
    if params.lut_dtype == "auto" and params.refine == "none":
        # one resolution point for the fp8-default policy: every scan
        # tier below (LUT kernel, grouped, per-query) and the
        # sharded dispatch receive a concrete dtype. Refined searches
        # resolve at the _route_refined RE-ENTRY instead, where k is
        # the oversampled k_cand = k·refine_ratio — the selection
        # width the fp8 slack floor (FP8_LUT_MIN_SLACK) is defined
        # over; resolving here with the final k would overstate the
        # slack by refine_ratio×. A filter's selectivity discounts the
        # slack the same way: only surviving candidates fill the bins.
        params = dataclasses.replace(params, lut_dtype=resolve_lut_dtype(
            "auto", min(params.n_probes, index.n_lists), k,
            selectivity=_filter_selectivity(filter_bitset)))
    from raft_tpu.neighbors import ivf_common as ic

    _divf = ic.sharded_dispatch(index, mesh, "ShardedIvfPq")
    if _divf is not None:
        return _divf.search_ivf_pq(params, index, queries, k, mesh,
                                   axis=mesh_axis, dataset=dataset,
                                   merge=merge, filter_bitset=filter_bitset)
    expects(queries.ndim == 2 and queries.shape[1] == index.dim,
            "queries must be [m, %d]", index.dim)
    _faults.faultpoint("ivf_pq.search")
    if params.refine != "none":
        return _route_refined(index, queries, k, params, filter_bitset,
                              dataset)
    n_probes = min(params.n_probes, index.n_lists)
    B = queries.shape[0]
    mode = params.scan_mode
    if mode == "auto":
        # an explicit pallas tier request is a grouped-scan request: the
        # LUT kernel is segment-structured, batch size notwithstanding
        mode = ("grouped" if (B * n_probes >= 2 * index.n_lists
                              or params.scan_select == "pallas")
                else "per_query")
    if mode == "grouped":
        from raft_tpu.neighbors import ivf_common as ic

        # segmented scan: the table shape is a function of (B, n_probes,
        # n_lists, seg) alone — no probe histogram, no host sync, one
        # jitted program per static config (see ivf_common docstring)
        seg = ic.SEGMENT_SIZE
        pairs = B * n_probes
        n_seg = ic.n_segments(pairs, index.n_lists, seg)
        L = index.max_list_size
        kk = min(k, L)
        from raft_tpu.ops import pallas_kernels as _pk

        # fused Pallas LUT-scan tier: explicit scan_select="pallas", or
        # the approx tier auto-upgraded for oversampled shapes where the
        # XLA scan's HBM transients are hostile and no recon cache
        # exists to shortcut the decode (the DEEP-100M regime)
        # the LUT tier emits at most LUT_SCAN_BINS candidates per probed
        # list — with too few probes for the requested k it would pad
        # the tail with -1s where the XLA tiers return real neighbors.
        # Filtered searches RIDE the tier: the kernel streams the packed
        # per-candidate filter bytes beside the codes and masks filtered
        # candidates to the sentinel BEFORE bin selection, so the bins
        # hold only kept candidates (the retired filter_bitset fallback)
        filtered = filter_bitset is not None
        lut_desired = (params.scan_select == "pallas"
                       or (params.scan_select == "approx"
                           and index.packed_recon is None
                           and (n_probes >= 64 or k >= 400)))
        lut_serviceable = n_probes * _pk.LUT_SCAN_BINS >= k
        want_lut = lut_desired and lut_serviceable
        select_impl = params.scan_select
        if lut_desired and not lut_serviceable:
            # the fallback counter records WHY the tier lost (satellite:
            # the dispatch counter alone shows only the winner)
            _count_lut_fallback("bin_capacity")
            if params.scan_select == "pallas":
                _warn_lut_fallback("bin_capacity")
                select_impl = "approx"
        if want_lut:
            mem_ok = (ic.lut_scan_mem_ok(n_seg, seg, index.rot_dim,
                                         pairs, _pk.LUT_SCAN_BINS)
                      and (not filtered
                           or ic.filtered_scan_mem_ok(index.n_lists, L))
                      and not _faults.forced("ivf_pq.scan.mem_guard"))
            kernel_ok = mem_ok and _pk.pallas_lut_scan_wanted(
                index.pq_dim, index.pq_book_size, index.pq_len,
                packed_nbytes(index.pq_dim, index.pq_bits),
                index.packed_codes.shape[-1], L, index.rot_dim,
                seg=seg, lut_dtype=params.lut_dtype, filtered=filtered)
            if index.codebook_kind == "per_subspace" and kernel_ok:
                _count_scan_dispatch("pallas_lut", filtered=filtered)
                with span("scan") as _sp:
                    out = _search_lut_pallas(
                        index, queries, k, n_probes, seg, n_seg,
                        filter_bits=filter_bitset,
                        lut_dtype=params.lut_dtype)
                    _sp.attach(out)
                return out
            reason = ("per_cluster" if index.codebook_kind != "per_subspace"
                      else "mem_guard" if not mem_ok else "kernel_ineligible")
            _count_lut_fallback(reason)
            if reason == "mem_guard":
                # the static half of the degradation policy: a guard
                # declining the fused tier before it OOMs records the
                # same degrade.steps move the reactive ladder would
                # (explicit pallas requests land on approx, see below)
                to_impl = ("approx" if params.scan_select == "pallas"
                           else select_impl)
                _degrade.note_step("ivf_pq.search", "pallas_lut",
                                   f"grouped_{to_impl}", "mem_guard")
            if params.scan_select == "pallas":
                # an EXPLICIT pallas request that the kernel can't serve
                # (per_cluster codebooks, unsupported layout, off-TPU, or
                # a memory guard) must not silently land on the exact
                # grouped scan — the most HBM-hostile engine at exactly
                # the oversampled shapes this tier exists for. Fall back
                # to the recall-targeted approx tier (which re-enables
                # segk when a recon cache exists) and say so.
                _warn_lut_fallback(reason)
                select_impl = "approx"
        if params.scan_mode == "grouped" or ic.grouped_mem_ok(
                n_seg, seg, kk, pairs):
            chunk = ic.fit_seg_chunk(seg, L, index.rot_dim,
                                     params.list_chunk)
            approx = select_impl == "approx"
            # segk rides filtered searches through a SENTINEL-MASKED id
            # table (filtered slots become the -1 invalid id before the
            # kernel's bin pre-selection — _search_grouped builds it);
            # the [n_lists, L] bool+i32 transient is the 5-byte/slot
            # admission filtered_scan_mem_ok budgets
            segk = (approx and index.packed_recon is not None
                    and (filter_bitset is None
                         or ic.filtered_scan_mem_ok(index.n_lists, L,
                                                    slot_bytes=5))
                    and _pk.pallas_segmented_wanted(kk, L, index.rot_dim,
                                                    S=seg))
            wants = (not approx) and _pk.pallas_grouped_wanted(
                kk, L, index.rot_dim, bq=seg)
            _count_scan_dispatch("segk" if segk else
                                 ("grouped_pallas" if wants
                                  else "grouped_xla"), filtered=filtered)
            return _search_grouped(index, queries, k, n_probes, seg,
                                   n_seg, chunk, use_pallas=wants,
                                   filter_bits=filter_bitset,
                                   select_impl=select_impl,
                                   select_recall=params.scan_recall,
                                   use_segk=segk)
    _count_scan_dispatch("per_query", filtered=filter_bitset is not None)
    return _search_impl(index, queries, k, n_probes,
                        _fit_query_tile(params.query_tile, n_probes, index),
                        filter_bits=filter_bitset, lut_dtype=params.lut_dtype)


@traced("raft_tpu.ivf_pq.search_resilient")
def search_resilient(index: IvfPqIndex, queries: jax.Array, k: int,
                     params: Optional[SearchParams] = None,
                     filter_bitset: Optional[jax.Array] = None,
                     dataset=None,
                     deadline=None) -> Tuple[jax.Array, jax.Array]:
    """:func:`search` behind the standard degradation ladder
    (:mod:`raft_tpu.robust.degrade`): a ``RESOURCE_EXHAUSTED`` walks
    halve-batch → bf16 LUT → fp8 LUT → decline fused tier → host
    gather (then
    keeps halving) instead of crashing the request, recording every
    move in ``degrade.steps{site=ivf_pq.search,from=,to=,reason=}``.
    Results are the degraded configuration's results — batch splitting
    is exact (each query's math is independent); the bf16-LUT and
    declined-tier rungs trade the documented precision/speed margins.
    Serving loops should call this; offline sweeps that prefer a crash
    to a silently degraded number keep calling :func:`search`.

    ``deadline`` (a :class:`raft_tpu.robust.retry.Deadline` — ISSUE 14)
    is the request's ONE shared wall-clock budget: the ladder checks it
    before every re-attempt and between split sub-batches, so degraded
    retries can no longer stack past the SLO the caller promised
    (:class:`~raft_tpu.robust.retry.DeadlineExceeded` on exhaustion,
    counted ``degrade.deadline_abort{site=ivf_pq.search}``)."""
    if params is None:
        params = SearchParams()
    if params.lut_dtype == "auto":
        # resolve BEFORE the ladder, exactly as the wrapped search
        # would (refined searches select over k_cand = k·refine_ratio):
        # the LUT rungs must see the concrete dtype dispatch runs with
        # — on a TPU oversampled shape "auto" is already fp8, and
        # pinning bf16 over that would ENLARGE the operand under the
        # very memory pressure the ladder exists to relieve (both LUT
        # rungs correctly skip instead)
        kr = k if params.refine == "none" else max(
            k, int(round(k * params.refine_ratio)))
        params = dataclasses.replace(params, lut_dtype=resolve_lut_dtype(
            "auto", min(params.n_probes, index.n_lists), kr,
            selectivity=_filter_selectivity(filter_bitset)))
    queries = jnp.asarray(queries)
    return _degrade.run_with_degradation(
        _degrade.batched_search_call(search, index, queries, k,
                                     filter_bitset, deadline=deadline,
                                     site="ivf_pq.search"),
        {"params": params, "dataset": dataset},
        _degrade.standard_search_ladder(queries.shape[0], has_lut=True),
        site="ivf_pq.search", deadline=deadline)


def _fit_query_tile(want: int, n_probes: int, index: IvfPqIndex) -> int:
    """Largest per_query tile ≤ ``want`` whose per-tile candidate tensors
    stay bounded: the f32 [t, n_probes, L, rot_dim] recon gather on the
    recon-dot path, or the unpacked codes + one-hot operand feed on the
    LUT path — sized on the wider of the two at 4 bytes."""
    L = index.max_list_size
    width = max(index.pq_dim,
                index.rot_dim if index.packed_recon is not None else 0)
    return max(1, min(want, (1 << 30) // max(1, n_probes * L * width * 4)))


# ---------------------------------------------------------------------------
# serialization (reference: neighbors/ivf_pq_serialize.cuh)
# ---------------------------------------------------------------------------

def save(index: IvfPqIndex, path: str) -> None:
    arrays = {"centers": index.centers,
              "centers_rot": index.centers_rot,
              "rotation": index.rotation,
              "codebooks": index.codebooks,
              "packed_codes": index.packed_codes,
              "packed_ids": index.packed_ids,
              "packed_norms": index.packed_norms,
              "list_sizes": index.list_sizes}
    # the bf16 cache is derived data — rebuilt on load, never serialized
    ser.save_arrays(path, "ivf_pq", _SERIAL_VERSION,
                    {"metric": index.metric,
                     "has_recon": index.packed_recon is not None,
                     "codebook_kind": index.codebook_kind,
                     "pq_bits": index.pq_bits,
                     "pq_dim": index.pq_dim}, arrays)


def load(path: str) -> IvfPqIndex:
    version, meta, a = ser.load_arrays(path, "ivf_pq")
    expects(version in (1, _SERIAL_VERSION),
            "unsupported ivf_pq version %d", version)
    # v1 files predate codebook_kind/pq_bits/packed codes: byte-per-
    # subspace per_subspace layout, recoverable from the defaults.
    # Billion-scale arrays upload in row slices (see to_device_chunked).
    pc = a["packed_codes"]
    pq_dim_meta = int(meta.get("pq_dim", 0)) or pc.shape[-1]
    nb = packed_nbytes(pq_dim_meta, int(meta.get("pq_bits", 8)))
    folded = pc.ndim == 3 and pc.shape[-1] != nb
    if (not folded and nb < 128 and pc.nbytes > (1 << 30)
            and (pc.shape[1] * nb) % 128 == 0):
        # lane-fold big code arrays (free row-major host view): a u8
        # trailing dim < 128 pads to 128 lanes on TPU — 2× the HBM
        pc = pc.reshape(pc.shape[0], -1, 128)
        folded = True
    packed_codes = ser.to_device_chunked(pc)
    index = IvfPqIndex(
        centers=jnp.asarray(a["centers"]),
        centers_rot=jnp.asarray(a["centers_rot"]),
        rotation=jnp.asarray(a["rotation"]),
        codebooks=jnp.asarray(a["codebooks"]),
        packed_codes=packed_codes,
        packed_ids=ser.to_device_chunked(a["packed_ids"]),
        packed_norms=ser.to_device_chunked(a["packed_norms"]),
        list_sizes=jnp.asarray(a["list_sizes"]),
        metric=meta["metric"],
        codebook_kind=meta.get("codebook_kind", "per_subspace"),
        pq_bits=int(meta.get("pq_bits", 8)),
        pq_dim_static=pq_dim_meta,
        codes_folded=folded)
    if meta.get("has_recon"):
        index = index.replace(packed_recon=_build_recon_cache(index))
    return index
