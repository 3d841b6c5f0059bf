"""Refine — exact re-ranking of ANN candidate lists.

TPU-native counterpart of ``raft::neighbors::refine`` (refine-inl.cuh;
device kernel detail/refine_device.cuh, host/OpenMP variant
detail/refine_host-inl.hpp). Used after IVF-PQ search to recover recall
lost to quantization (the reference's refinement_rate pattern: search
k·rate candidates, refine down to k).

Tier dispatch (``refine.dispatch{impl=...}`` obs counter; decision
table in docs/api_reference.md):

- ``pallas_gather`` — the fused gather-refine kernel
  (ops.pallas_kernels.gather_refine_topk): candidate rows stream
  HBM→VMEM per tile and the exact epilogue + top-k run on-chip, so the
  ``[m, C, d]`` gather buffer never exists (7.7 GB at batch 10000 ×
  k_cand 2000 × d 96 — the accumulator-OOM shape of the oversampled
  DEEP-100M configs). Auto-on for TPU oversampled shapes; env override
  ``RAFT_TPU_PALLAS_REFINE`` (tri-state).
- ``xla_gather`` — gather each query's candidate rows and recompute
  exact distances with one batched MXU contraction, then select.
- ``host_gather`` / ``provider_regen`` — the host-resident-base tiers
  (:func:`refine_gathered`, :func:`refine_provider`): the gather runs
  on the host / regenerates device blocks BY DESIGN (memmap bases that
  do not fit HBM), so the fused device tier does not apply.
- ``tiered_prefetch`` — the memory-tier pipeline (ISSUE 17,
  :mod:`raft_tpu.neighbors.tiered`): host-resident bases whose
  candidate rows are fetched host→HBM by a background reader
  overlapped under the next sub-batch's scan; :func:`refine_landed` is
  its re-rank entry (rows already on device — same exact epilogue,
  zero extra gather).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.errors import expects
from raft_tpu.core.tracing import annotate, traced, span
from raft_tpu.distance.types import DistanceType, resolve_metric
from raft_tpu.matrix import select_k as _select_k
from raft_tpu.obs import spans as _obs_spans
from raft_tpu.ops import pallas_kernels as _pk
from raft_tpu.robust import degrade as _degrade
from raft_tpu.robust import faults as _faults
from raft_tpu.utils.precision import get_precision


def _check_candidates(queries, candidates, k: int) -> None:
    """Shared argument validation for every refine entry point — an
    oversized k or an empty candidate axis otherwise surfaces deep in
    the jitted program as an opaque take_along_axis/einsum error."""
    expects(candidates.ndim == 2, "candidates must be [m, n_candidates]")
    expects(candidates.shape[1] > 0,
            "candidates must have a non-empty candidate axis "
            "(got shape %s)", tuple(candidates.shape))
    expects(queries.shape[0] == candidates.shape[0],
            "queries/candidates row mismatch: %d queries vs %d candidate "
            "rows", queries.shape[0], candidates.shape[0])
    expects(k <= candidates.shape[1],
            "k=%d > n_candidates=%d — refine can only re-rank the "
            "candidates it is given (search more candidates or lower k)",
            k, candidates.shape[1])


def _check_base_dim(base, queries) -> None:
    """Feature-dim agreement between the re-rank base and the queries —
    a mismatch otherwise dies in the einsum (or the Pallas block spec)
    with an opaque shape error. Row-count agreement stays the caller's
    contract: candidate ids past the base clamp to its last row (the
    historical XLA-gather semantics), and checking it here would cost a
    device sync per call on indexed structures."""
    shape = getattr(base, "shape", None)
    expects(shape is not None and len(shape) == 2
            and shape[1] == queries.shape[1],
            "dataset/queries feature-dim mismatch: dataset shape %s vs "
            "%d-dim queries", tuple(shape) if shape else None,
            queries.shape[1])


# Stage scope of the re-rank programs (op_name metadata only), whichever
# index's search handed over the candidates; the fused Pallas re-rank
# takes the same one.
_REFINE = _pk.REFINE_SCOPE


@partial(jax.jit, static_argnames=("k", "metric"))
@annotate(_REFINE)
def _refine_impl(dataset, queries, candidates, k: int, metric: str):
    safe_cand = jnp.maximum(candidates, 0)
    cand_rows = dataset[safe_cand].astype(jnp.float32)    # [m, C, d]
    return _refine_rows(cand_rows, queries, candidates, k, metric)


@partial(jax.jit, static_argnames=("k", "metric"))
@annotate(_REFINE)
def _refine_rows(cand_rows, queries, candidates, k: int, metric: str):
    mt = resolve_metric(metric)
    q = jnp.asarray(queries, jnp.float32)
    scores = jnp.einsum("md,mcd->mc", q, cand_rows,
                        precision=get_precision(),
                        preferred_element_type=jnp.float32)
    if mt == DistanceType.InnerProduct:
        dists = scores
        invalid = -jnp.inf
        select_min = False
    elif mt == DistanceType.CosineExpanded:
        qn = jnp.sqrt(jnp.maximum(jnp.sum(q * q, 1), 1e-30))
        cn = jnp.sqrt(jnp.maximum(jnp.sum(cand_rows**2, -1), 1e-30))
        dists = 1.0 - scores / (qn[:, None] * cn)
        invalid = jnp.inf
        select_min = True
    else:
        q_sq = jnp.sum(q * q, axis=1)
        c_sq = jnp.sum(cand_rows**2, axis=-1)
        dists = jnp.maximum(q_sq[:, None] + c_sq - 2.0 * scores, 0.0)
        if mt == DistanceType.L2SqrtExpanded:
            dists = jnp.sqrt(dists)
        invalid = jnp.inf
        select_min = True
    dists = jnp.where(candidates >= 0, dists, invalid)
    vals, pos = _select_k(dists, k, select_min=select_min)
    ids = jnp.take_along_axis(candidates, pos, axis=1)
    return vals, ids


@partial(jax.jit, static_argnames=("metric",))
@annotate(_REFINE)
def _gather_keys_to_dists(keys, ids, metric: str):
    """Kernel keys → reported distances: the gather-refine kernel emits
    minimized sort keys (l2: squared distance, ip: −score, cos: cosine
    distance); recover :func:`_refine_rows`' reporting convention."""
    mt = resolve_metric(metric)
    if mt == DistanceType.InnerProduct:
        return -keys, ids  # +inf invalid keys flip to -inf, as the XLA path
    if mt == DistanceType.L2SqrtExpanded:
        return jnp.sqrt(keys), ids
    return keys, ids


def _fused_refine_wanted(dataset, queries, candidates, k: int,
                         filtered: bool = False) -> bool:
    """True when the fused gather-refine tier serves this call: a
    device-resident 2-D dataset whose dtype the row DMAs stream (f32 or
    the bf16 recon cache) and a shape :func:`pallas_gather_refine_wanted`
    accepts (``filtered`` adds the per-candidate bitset-word scratch to
    its VMEM model)."""
    from raft_tpu.neighbors import ivf_common as ic

    if not isinstance(dataset, jax.Array) or dataset.ndim != 2:
        return False
    if dataset.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    mem_ok = ic.gather_refine_mem_ok(dataset.shape[0], dataset.shape[1],
                                     dataset.dtype.itemsize,
                                     m=candidates.shape[0],
                                     C=candidates.shape[1])
    if _faults.forced("refine.mem_guard"):  # CI-testable decline path
        mem_ok = False
    if not mem_ok:
        # the static half of the degradation policy (robust.degrade):
        # the guard's pre-emptive tier decline counts the same
        # degrade.steps move a reactive OOM walk would
        _degrade.note_step("refine", "pallas_gather", "xla_gather",
                           "mem_guard")
        return False
    return _pk.pallas_gather_refine_wanted(
        candidates.shape[0], candidates.shape[1], dataset.shape[1], k,
        itemsize=dataset.dtype.itemsize, filtered=filtered)


def _refine_fused(dataset, queries, candidates, k: int, mt: DistanceType,
                  filter_bits=None):
    met = ("ip" if mt == DistanceType.InnerProduct
           else "cos" if mt == DistanceType.CosineExpanded else "l2")
    with span("fused_scan") as _sp:
        keys, ids = _pk.gather_refine_topk(
            dataset, queries, jnp.asarray(candidates), k, met,
            filter_bits=filter_bits, interpret=not _pk._on_tpu())
        out = _gather_keys_to_dists(keys, ids, mt.value)
        _sp.attach(out)
    return out


@traced("raft_tpu.refine")
def refine(
    dataset: jax.Array,
    queries: jax.Array,
    candidates: jax.Array,
    k: int,
    metric="sqeuclidean",
    filter_bits=None,
) -> Tuple[jax.Array, jax.Array]:
    """Re-rank ``candidates`` [m, n_cand] (row ids into ``dataset``, -1 =
    invalid) down to the exact top-k (reference: refine-inl.cuh).

    Dispatches between the fused Pallas gather-refine kernel (streamed
    candidate rows, no ``[m, C, d]`` buffer — auto on TPU for
    oversampled shapes, override ``RAFT_TPU_PALLAS_REFINE``) and the
    XLA gather+einsum path; both share exact semantics (module
    docstring has the tier table). Returns (distances [m, k],
    ids [m, k]).

    ``filter_bits``: optional packed uint32 bitset over dataset rows
    (``core.bitset`` layout) — candidates whose bit is clear are
    excluded like invalid ids. The fused tier tests each candidate
    in-kernel against its bitset word (fetched by the row-DMA queue);
    the XLA tier sentinel-masks the candidate table first. Oversampled
    searches hand refine pre-filtered candidates already — the filter
    here is the enforcement site for DIRECT callers re-ranking an
    unfiltered candidate list.
    """
    _check_candidates(queries, candidates, k)
    _check_base_dim(dataset, queries)
    mt = resolve_metric(metric)
    filtered = filter_bits is not None
    if _fused_refine_wanted(dataset, queries, candidates, k,
                            filtered=filtered):
        if filtered:
            _obs_spans.count_dispatch("refine", "pallas_gather",
                                      filtered="1")
        else:
            _obs_spans.count_dispatch("refine", "pallas_gather")
        return _refine_fused(dataset, queries, candidates, k, mt,
                             filter_bits=filter_bits)
    if filtered:
        from raft_tpu.neighbors.sample_filter import passes

        # sentinel-mask before the gather: a filtered candidate becomes
        # the -1 invalid id _refine_rows already poisons to ±inf
        candidates = jnp.where(passes(filter_bits, candidates),
                               candidates, -1)
        _obs_spans.count_dispatch("refine", "xla_gather", filtered="1")
    else:
        _obs_spans.count_dispatch("refine", "xla_gather")
    return _refine_impl(dataset, queries, candidates, k, mt.value)


@traced("raft_tpu.refine_landed")
def refine_landed(
    cand_rows: jax.Array,
    queries: jax.Array,
    candidates: jax.Array,
    k: int,
    metric="sqeuclidean",
) -> Tuple[jax.Array, jax.Array]:
    """Re-rank against candidate rows ALREADY LANDED on device — the
    tiered prefetch pipeline's re-rank entry (ISSUE 17,
    :mod:`raft_tpu.neighbors.tiered`): the ``[m, C, d]`` f32 rows were
    gathered host-side by the background reader (bit-identical to
    :func:`refine_gathered`'s gather) and device_put ahead of time, so
    this entry runs only the exact epilogue (same jitted
    ``_refine_rows`` program as every other tier — same results)."""
    _check_candidates(queries, candidates, k)
    shape = getattr(cand_rows, "shape", None)
    expects(shape is not None and len(shape) == 3
            and tuple(shape[:2]) == tuple(candidates.shape)
            and shape[2] == queries.shape[1],
            "cand_rows shape %s does not match candidates %s × dim %d",
            tuple(shape) if shape else None, tuple(candidates.shape),
            queries.shape[1])
    _obs_spans.count_dispatch("refine", "tiered_prefetch")
    mt = resolve_metric(metric)
    return _refine_rows(cand_rows, queries, jnp.asarray(candidates), k,
                        mt.value)


@partial(jax.jit, donate_argnums=(0,))
def _fill_rows(buf, blk, lidx, pos):
    """Scatter gathered block rows into the candidate-row buffer
    (module-level so the jit cache hits across refine_provider calls;
    the last ``pos`` slot is the dump row for padding)."""
    return buf.at[pos].set(blk[lidx].astype(jnp.float32))


@traced("raft_tpu.refine_provider")
# the provider path exists to gather candidate rows on the HOST (memmap
# bases) — its device_get round-trip is the point, not a leak
def refine_provider(  # graftlint: disable-fn=GL01
    provider,
    queries: jax.Array,
    candidates: jax.Array,
    k: int,
    metric="sqeuclidean",
) -> Tuple[jax.Array, jax.Array]:
    """Re-rank against a device-chunk provider (bench.dataset.
    DeviceSyntheticChunks): regenerate each fixed-size generation block
    ON DEVICE and gather the candidate rows out of it — an EXACT f32
    re-rank with zero host traffic and no quantization error (the SQ8
    refine file loses ~1e-2 per squared distance, which on dense
    synthetic data exceeds neighbor gaps and caps recall; reference:
    the full-precision refinement_rate path, refine-inl.cuh).

    Cost is one generation pass over the provider's blocks (pipelined
    device programs; the gathered-row buffer is O(m·C·d) in HBM).
    """
    import numpy as np

    _check_candidates(queries, candidates, k)
    _check_base_dim(provider, queries)
    _obs_spans.count_dispatch("refine", "provider_regen")
    mt = resolve_metric(metric)
    cand = np.asarray(candidates)
    m, C = cand.shape
    n, d = provider.shape
    c = provider.chunk_rows
    n_blocks = -(-n // c)
    flat = cand.reshape(-1)
    safe = np.clip(flat, 0, n - 1)
    block_of = safe // c
    counts = np.bincount(block_of, minlength=n_blocks)
    P = max(8, int(counts.max()))  # one compiled shape for every block

    buf = jnp.zeros((m * C + 1, d), jnp.float32)
    order = np.argsort(block_of, kind="stable")
    starts = np.searchsorted(block_of[order], np.arange(n_blocks + 1))
    for bi in range(n_blocks):
        sel = order[starts[bi]:starts[bi + 1]]
        if sel.size == 0:
            continue
        lidx = np.zeros((P,), np.int32)
        lidx[:sel.size] = safe[sel] - bi * c
        pos = np.full((P,), m * C, np.int32)
        pos[:sel.size] = sel
        buf = _fill_rows(buf, provider._block(bi), jnp.asarray(lidx),
                         jnp.asarray(pos))
    rows = buf[:m * C].reshape(m, C, d)
    return _refine_rows(rows, queries, jnp.asarray(cand), k, mt.value)


@traced("raft_tpu.refine_gathered")
# host-side candidate-row gather by design (memmap bases — jitted refine
# would materialize the whole base in HBM)
def refine_gathered(  # graftlint: disable-fn=GL01
    host_base,
    queries: jax.Array,
    candidates: jax.Array,
    k: int,
    metric="sqeuclidean",
    dequant=None,
) -> Tuple[jax.Array, jax.Array]:
    """Re-rank against a HOST-resident (possibly memmapped) dataset:
    gather only each query's candidate rows on the host — O(m·C·d) pages
    touched, never the whole base — then re-rank on device (reference:
    the host refine path, detail/refine_host-inl.hpp, used by CAGRA
    builds and billion-scale benches where the base doesn't fit).

    ``dequant=(scale, zero)``: ``host_base`` holds int8 scalar-quantized
    rows (x ≈ zero + scale·code, per-dim) — the billion-scale refine
    file is 4× smaller and re-ranking ~20 candidates to top-k tolerates
    SQ8 precision easily."""
    import numpy as np

    _check_candidates(queries, candidates, k)
    _check_base_dim(host_base, queries)
    _obs_spans.count_dispatch("refine", "host_gather")
    mt = resolve_metric(metric)
    cand = np.asarray(candidates)
    safe = np.clip(cand, 0, host_base.shape[0] - 1)
    rows = np.asarray(host_base[safe.reshape(-1)], np.float32).reshape(
        cand.shape[0], cand.shape[1], host_base.shape[1])
    if dequant is not None:
        scale, zero = dequant
        rows = rows * np.asarray(scale)[None, None, :] \
            + np.asarray(zero)[None, None, :]
    return _refine_rows(jnp.asarray(rows), queries, jnp.asarray(cand),
                        k, mt.value)
