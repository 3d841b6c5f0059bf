"""List-centric IVF scan machinery — the TPU-native inversion of the
reference's per-query list scan.

The reference's search kernels (ivf_flat_interleaved_scan-inl.cuh,
ivf_pq_compute_similarity-inl.cuh) are per-query: one CTA walks the
query's probed lists through shared memory. On a TPU that structure is
wrong twice over: per-query work is too small for the MXU, and each
query re-reads its lists from HBM.

The TPU-native structure inverts the loop — **group the query batch by
probed list**, then stream each probed list through the MXU in
fixed-size *segments* of its query queue:

1. probe selection gives ``probes [B, n_probes]`` (queries → lists);
2. :func:`segment_probes` buckets the (query, probe) pairs into
   segments of ``seg`` pairs, each segment owned by ONE list, via one
   stable sort — the same trick the index build uses to pack rows;
3. the scan loops over *segment chunks*: gather each segment's list
   block and its ``seg`` queries, run one batched ``[seg, d] × [d, L]``
   contraction per segment on the MXU, take a per-(slot, list) top-k;
4. results are gathered back to ``[B, n_probes, k]`` pair order (a
   gather, not a scatter — TPUs gather much faster than they scatter)
   and a final select_k merges each query's n_probes·k candidates.

Segments are the load-balancing device: a skew-hot list simply owns
more segments, a cold list at most one — total padded work is bounded
by ``pairs + n_lists·seg`` slots regardless of skew. (The earlier
design padded every list's queue to the batch's max per-list load,
which both wasted up to ~70× FLOPs under skew and needed a host sync
to read that load; the segmented table is **statically shaped** from
``(B, n_probes, n_lists)`` alone, so a whole search — probe selection,
segmenting, scan, merge — compiles into ONE jitted program with no
host round-trip.)

HBM traffic: each list block is read once per *owned segment* per
batch instead of once per *probing query* — the amortization that
makes IVF beat brute force on TPU at large batch sizes.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


# Default segment size: one MXU-friendly block of queries per segment
# (matches the Pallas grouped kernel's bq block).
SEGMENT_SIZE = 128


def note_rows_dropped(index: str, n: int) -> None:
    """Count ``n`` rows a build could not hold in its capped lists (a
    list overflow, or every spill choice full) under the obs counter
    ``ivf.build.rows_dropped{index=...}``, where the build warns.
    Recorded only when obs is on (the ``count_dispatch`` convention)."""
    from raft_tpu.obs import spans as _obs_spans

    if n and _obs_spans.enabled():
        _obs_spans.registry().inc("ivf.build.rows_dropped", float(n),
                                  labels={"index": index})


def note_list_fill(index: str, list_sizes, capacity: int) -> None:
    """Set the obs gauge ``ivf.build.list_fill{index=...}`` to the rows
    an index holds over its padded slots (``n_lists * capacity``): the
    share of every probed block's rows that is data, not padding.
    Recorded only when obs is on (reading the sizes waits for the
    build)."""
    from raft_tpu.obs import spans as _obs_spans

    if _obs_spans.enabled():
        sizes = np.asarray(list_sizes)
        _obs_spans.registry().set(
            "ivf.build.list_fill",
            float(sizes.sum()) / max(1, sizes.size * int(capacity)),
            labels={"index": index})


def sharded_dispatch(index, mesh, cls_name: str):
    """The pod-scale dispatch gate shared by the IVF search entries:
    returns the ``raft_tpu.parallel.ivf`` module when ``(index, mesh)``
    route to the sharded tier, ``None`` for the single-chip path.

    A sharded index can only exist if ``parallel.ivf`` is already
    imported, so the gate checks ``sys.modules`` first — plain
    single-chip searches never pay the parallel-subtree import.
    Validates the pairing (a sharded index without its mesh, or a
    ``mesh=`` with a single-chip index, is a caller error); per-entry
    capability checks (filters, refine) stay with the caller."""
    import sys

    from raft_tpu.core.errors import expects as _expects

    if mesh is None and "raft_tpu.parallel.ivf" not in sys.modules:
        return None
    from raft_tpu.parallel import ivf as _divf

    cls = getattr(_divf, cls_name)
    if mesh is None and not isinstance(index, cls):
        return None
    _expects(isinstance(index, cls),
             "mesh= dispatch needs a parallel.%s index (got %s)",
             cls_name, type(index).__name__)
    _expects(mesh is not None,
             "a %s index needs search(..., mesh=...)", cls_name)
    return _divf


def n_segments(pairs: int, n_lists: int, seg: int) -> int:
    """Static upper bound on the segment count: every list owns
    ``ceil(load/seg)`` segments, and ``sum ceil(load/seg) <=
    floor(pairs/seg) + n_lists`` for any load histogram — so the table
    shape depends only on (B, n_probes, n_lists, seg), never on the
    data. That is what keeps the whole search one jitted program."""
    return pairs // seg + n_lists


def segment_probes(probes: jax.Array, n_lists: int, seg: int, n_seg: int):
    """Bucket (query, probe) pairs into per-list segments (trace-time;
    called inside the search jit).

    One stable sort of the flattened probe table gives each pair its
    within-list rank; segment ids follow from a cumsum of per-list
    segment counts. TPU note: everything here is sorts + GATHERS — the
    segment table is filled by computing, per (segment, slot), which
    sorted pair occupies it (``i = starts[list] + local_seg·seg +
    slot``), and pair-order addresses come from the sort's inverse
    permutation (a second argsort). XLA scatters serialize on TPU
    (~100 ms at 10⁵ elements, measured), so the scatter formulation of
    the same table costs more than the whole rest of the scan.

    Parameters
    ----------
    probes : [B, P] int32 list ids per query.
    n_lists : number of inverted lists.
    seg : segment capacity (pairs per segment, static).
    n_seg : static segment-table height (:func:`n_segments`).

    Returns
    -------
    seg_list : [n_seg] int32 — which list each segment scans (unused
        segments point at an arbitrary list; their slots are all -1).
    seg_q : [n_seg, seg] int32 — query ids, -1 pad.
    pair_seg, pair_slot : [B, P] int32 — each pair's (segment, slot)
        address, for gathering results back to pair order.
    """
    B, P = probes.shape
    BP = B * P
    l_flat = probes.reshape(-1).astype(jnp.int32)
    # sort_key_val, not argsort+gather: values ride the sort for free
    # (an argsort plus the sorted_l re-gather measured ~3× the cost)
    iota = jnp.arange(BP, dtype=jnp.int32)
    sorted_l, order = jax.lax.sort_key_val(l_flat, iota)
    starts = jnp.searchsorted(sorted_l, jnp.arange(n_lists, dtype=jnp.int32))
    counts = jnp.diff(jnp.append(starts, BP)).astype(jnp.int32)
    segs_per_list = (counts + seg - 1) // seg
    seg_base = jnp.cumsum(segs_per_list) - segs_per_list  # exclusive
    # segment → owning list: rightmost list whose base is <= s (right-
    # side search steps over zero-segment lists, whose base repeats)
    seg_ids = jnp.arange(n_seg, dtype=jnp.int32)
    seg_list = jnp.clip(
        jnp.searchsorted(seg_base, seg_ids, side="right") - 1,
        0, n_lists - 1).astype(jnp.int32)
    # seg_q by gather: slot (s, j) holds sorted pair i = starts[l] +
    # local_seg·seg + j, valid while that rank is inside l's load
    # (covers both partial tail segments and unused segments, whose
    # local rank lands beyond the owning list's count)
    rank0 = (seg_ids - seg_base[seg_list]) * seg           # [n_seg]
    i0 = starts[seg_list] + rank0
    j = jnp.arange(seg, dtype=jnp.int32)
    rank = rank0[:, None] + j[None, :]
    valid = rank < counts[seg_list][:, None]
    q_of = (order // P).astype(jnp.int32)
    seg_q = jnp.where(
        valid, q_of[jnp.clip(i0[:, None] + j[None, :], 0, BP - 1)], -1)
    # pair-order addresses via the sort's inverse permutation
    rank_sorted = iota - starts[sorted_l].astype(jnp.int32)
    seg_sorted = seg_base[sorted_l] + rank_sorted // seg
    slot_sorted = rank_sorted % seg
    # inverse permutation by sorting the (seg, slot) addresses back to
    # pair order keyed on `order` — one sort carries both payloads, no
    # argsort + two pointwise gathers
    addr = seg_sorted * seg + slot_sorted
    _, addr_pair = jax.lax.sort_key_val(order, addr)
    return (seg_list, seg_q,
            (addr_pair // seg).reshape(B, P),
            (addr_pair % seg).reshape(B, P))


def gather_segment_results(seg_vals: jax.Array, seg_ids: jax.Array,
                           pair_seg: jax.Array, pair_slot: jax.Array
                           ) -> Tuple[jax.Array, jax.Array]:
    """Collect per-(segment, slot) top-k back into (query, probe) order:
    ``[n_seg, seg, kk] → [B, P, kk]``. Pure gather — every pair owns
    exactly one slot (the segmented table is drop-free by construction)."""
    return seg_vals[pair_seg, pair_slot], seg_ids[pair_seg, pair_slot]


def merge_bin_results(keys: jax.Array, kids: jax.Array,
                      pair_seg: jax.Array, pair_slot: jax.Array,
                      k: int, select_min: bool, invalid, recall: float):
    """Merge the scalar-prefetch kernel's per-bin output into final
    (distances [B, k], ids [B, k]) — shared by IVF-Flat and IVF-PQ.

    ``keys/kids [n_seg, S, nbins]`` are minimized sort keys + global
    candidate ids (-1 invalid, key +inf) from ops.pallas_kernels.
    segmented_scan_topk. Structure (each step sized by measurement on a
    1M×128 B=10000 search): gather each pair's WHOLE bin row to query
    order (a [B·P]-row block gather — row gathers are cheap; the former
    per-slot cut needed a [n_seg·S, nbins]→kk ``take_along_axis``
    whose ~3M pointwise picks measured 50–137 ms and dominated the
    whole search), one hardware top-k per query over its P·nbins
    candidates, then resolve the k winning ids with a [B, k]-pick
    gather (~100K picks ≈ 2 ms). Metric epilogues (sqrt, 1−cos) stay
    with the callers."""
    n_seg, seg, nbins = keys.shape
    B, P = pair_seg.shape
    kk = min(k, nbins)
    kq = min(k, P * kk)
    # per-slot cut on KEYS ONLY — the hardware top-k over 256-wide bin
    # rows is near-exact (measured end recall 0.999+; one cut over the
    # concatenated [B, P·nbins] row instead loses clustered winners to
    # reduction-tile collisions, measured 0.97)
    mk, sel = jax.lax.approx_min_k(keys.reshape(-1, nbins), kk,
                                   recall_target=recall)
    # gather the kk-wide cut (values + BIN POSITIONS) to query order —
    # c-class row gathers, ~1-4 ms. The former formulation gathered the
    # winning IDS here via a [n_seg·S, nbins]→kk take_along_axis whose
    # ~3M pointwise picks measured 50–137 ms and dominated the search.
    pv = mk.reshape(n_seg, seg, kk)[pair_seg, pair_slot].reshape(B, P * kk)
    pb = sel.reshape(n_seg, seg, kk)[pair_seg, pair_slot].reshape(B, P * kk)
    # exact final per-query cut over the P·kk survivors
    nv, pos2 = jax.lax.top_k(-pv, kq)
    # compose winners back to (seg, slot, bin) and resolve global ids —
    # [B, kq] picks only (~100K picks ≈ 2 ms)
    p_of = pos2 // kk
    bin_of = jnp.take_along_axis(pb, pos2, axis=1)
    seg_of = jnp.take_along_axis(pair_seg, p_of, axis=1)
    slot_of = jnp.take_along_axis(pair_slot, p_of, axis=1)
    out_ids = kids[seg_of, slot_of, bin_of]                 # [B, kq]
    out_vals = -nv if select_min else nv  # keys minimized; ip flips back
    out_vals = jnp.where(out_ids < 0, invalid, out_vals)
    if k > kq:
        pad = k - kq
        out_vals = jnp.pad(out_vals, ((0, 0), (0, pad)),
                           constant_values=invalid)
        out_ids = jnp.pad(out_ids, ((0, 0), (0, pad)), constant_values=-1)
    return out_vals, out_ids


# Auto-dispatch guard: fall back from grouped to per_query only when the
# segmented scan's allocations would be memory-hostile. Measured
# on-chip, grouped beats the gather-bound per_query path, so this is a
# memory bound, not a cost model. The accumulators are transient (freed
# after the pair gather), so the cap is sized against total HBM.
GROUPED_BYTES_CAP = 4 << 30
# Per-chunk budget for the scan's transients (the [chunk·seg, L]
# distance block and the gathered [chunk, L, d] list blocks); search()
# shrinks the segment chunk (down to 1) to honor it.
CHUNK_BYTES_TARGET = 256 << 20


def grouped_mem_ok(n_seg: int, seg: int, kk: int, pairs: int) -> bool:
    """True when the segmented scan's buffers fit the budget: the
    [n_seg, seg] int32 query table, the [n_seg, seg, kk] f32+i32
    per-slot top-k accumulators, and the [pairs, kk] gathered results
    live at the same time during gather_segment_results."""
    return (n_seg * seg * (4 + 8 * kk) + pairs * kk * 8) <= GROUPED_BYTES_CAP


def lut_scan_mem_ok(n_seg: int, seg: int, rot: int, pairs: int,
                    nbins: int = 256) -> bool:
    """HBM budget for the Pallas LUT-scan tier: the gathered per-segment
    queries [n_seg, seg, rot] f32, the kernel's [n_seg, seg, nbins]
    key+id bin tables, and the pair-order gather [pairs, nbins] f32+i32
    all live at once (everything else stays in VMEM — that is the tier's
    point). Shares GROUPED_BYTES_CAP with the XLA grouped scan."""
    qv = n_seg * seg * rot * 4
    bins = n_seg * seg * nbins * 8
    gathered = pairs * nbins * 8
    return qv + bins + gathered <= GROUPED_BYTES_CAP


def filtered_scan_mem_ok(n_lists: int, L: int,
                         slot_bytes: int = 1) -> bool:
    """HBM budget for a FILTERED fused-scan dispatch (the admission
    guard GL15 expects beside every streaming-kernel call site that
    hands a kernel filter operands). ``slot_bytes`` is the per-slot
    transient width of the filter operand the dispatching tier builds:
    1 for the LUT/ring tiers — a ``[n_lists, L]`` bool keep mask
    re-packed to ``[n_lists, ceil(L/8)]`` u8 byte rows
    (``sample_filter.list_filter_bytes``; ~2.5 GB at n = 2.2e9, inside
    the cap, so billion-scale filtered searches stay on the fused
    tier) — and 5 for segk's sentinel-masked i32 id table (mask +
    i32; segk's recon-cache precondition keeps its n small anyway).
    The packed byte rows are counted in both cases."""
    slots = n_lists * L
    return slots * slot_bytes + slots // 8 <= GROUPED_BYTES_CAP


def gather_refine_mem_ok(n: int, d: int, itemsize: int = 4,
                         m: int = 0, C: int = 0) -> bool:
    """HBM guard for the fused gather-refine tier (ops.pallas_kernels.
    gather_refine_topk): everything per-candidate stays in VMEM — the
    tier's point — but a dataset whose minor dim is not lane-aligned
    pays a PER-CALL padded ``[n, ceil(d/128)·128]`` HBM copy before the
    kernel (row DMAs address lane-tiled rows; the pad lives inside the
    jitted wrapper, so every refined search re-materializes it). Two
    checks: the copy must fit the shared transient cap, and — when the
    workload shape ``(m, C)`` is known — it must be smaller than the
    ``[m, C, d]`` f32 gather buffer the tier exists to avoid (a small
    re-rank against a huge unaligned dataset would otherwise pay MORE
    HBM than the einsum path it replaces). The XLA path pads per
    candidate row instead, so declining here is always serviceable."""
    if d % 128 == 0:
        return True
    dpad = -(-d // 128) * 128
    pad_copy = n * dpad * itemsize
    if pad_copy > GROUPED_BYTES_CAP:
        return False
    if m and C:
        return pad_copy <= m * C * d * 4
    return True


def tiered_refine_mem_ok(m_b: int, C: int, d: int,
                         depth: int = 2) -> bool:
    """HBM guard for the tiered prefetch-refine pipeline
    (neighbors.tiered): up to ``depth`` landed ``[m_b, C, d]`` f32
    candidate-row blocks parked in the prefetch queue plus the one
    being re-ranked live at once — the tier's whole HBM footprint (the
    base itself stays on the host, that is the point). Shares
    GROUPED_BYTES_CAP with the scan transients. Declining here is
    always serviceable: the serialized host gather (refine_gathered)
    holds exactly one block."""
    return (depth + 1) * m_b * C * d * 4 <= GROUPED_BYTES_CAP


def fit_seg_chunk(seg: int, L: int, d: int, want: int) -> int:
    """Largest segment chunk ≤ ``want`` whose per-step transients — the
    [chunk·seg, L] f32 distance block and the gathered [chunk, L, d]
    f32 list blocks — stay under CHUNK_BYTES_TARGET."""
    per_seg = L * 4 * (seg + d)
    return max(1, min(want, CHUNK_BYTES_TARGET // max(1, per_seg)))


# spill-cascade depth shared by every spilling builder (ivf_flat.build,
# ivf_pq.build, ivf_pq.build_chunked): a dense natural blob can fill
# its whole ~5-list neighborhood, so top-4 choices still drop rows a
# 6th keeps (measured on a 40%-mass Gaussian over 16 lists: depth 4
# dropped 158 rows, depth 6 dropped 0)
SPILL_DEPTH = 6


@partial(jax.jit, static_argnames=("n_lists", "cap"))
def spill_assignments(l1: jax.Array, l2: jax.Array, n_lists: int,
                      cap: int, *more) -> jax.Array:
    """Cap list loads by spilling overflow rows to their next-nearest
    lists — the TPU-native answer to padded-block waste.

    The padded [n_lists, L, ...] layout sizes L to the FATTEST list, so
    skewed assignments pay padding on every scan DMA (and at 100M rows
    can overflow HBM outright). Instead of dropping rows past the cap
    (the packers' old behavior) or padding to the skew, rows ranked
    ≥ cap in their first-choice list CASCADE to their next choice
    (``l2``, then each array in ``more``); rows that overflow every
    choice get the drop marker ``n_lists`` (callers warn). Deeper
    choice lists matter under natural-blob skew: one dense Gaussian
    holding ~40% of the rows fills its whole neighborhood of lists, so
    top-2 spilling still drops rows that a 3rd/4th choice keeps. A
    probe set covering a query's nearest lists almost always includes
    those next-nearest centers too, so the recall cost is marginal
    while L shrinks from ~(max load) to cap.

    All sorts + gathers (one stable sort pass per choice), jit-safe on
    host-sized inputs: [n] i32 argsorts are cheap even at 10⁸ rows.
    Settled rows never move again: ranks sort by (list, arrival
    generation) lexicographically, so later arrivals are the ones past
    the cap.
    """
    choices = (l2,) + more
    n = l1.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    g = len(choices) + 1                       # generations stride
    kmax = g * n_lists + g

    def ranks(keys, base):
        """Stable rank of each row within its group: ``keys`` orders
        rows inside and across groups, ``base`` is each row's group's
        smallest key (rank = sorted position − group start)."""
        sk, order = jax.lax.sort_key_val(keys, iota)
        base_sorted = base[order]
        starts = jnp.searchsorted(sk, jnp.arange(kmax, dtype=jnp.int32))
        rk_sorted = iota - starts[jnp.clip(base_sorted, 0, kmax - 1)]
        _, rk = jax.lax.sort_key_val(order, rk_sorted)
        return rk

    lab = l1.astype(jnp.int32)
    gen = jnp.zeros((n,), jnp.int32)
    for c, lc in enumerate(choices, start=1):
        rank = ranks(lab * g + gen, lab * g)
        over = rank >= cap
        lab = jnp.where(over, lc.astype(jnp.int32), lab)
        gen = jnp.where(over, c, gen)
    rank = ranks(lab * g + gen, lab * g)
    return jnp.where(rank >= cap, jnp.int32(n_lists), lab)


def pack_lists(row_arrays, labels: jax.Array, row_ids: jax.Array,
               n_lists: int, L: int, fill_values):
    """Device-side list packing (jit-safe) — the device twin of the host
    numpy packers in ivf_flat/ivf_pq (reference: encode+pack,
    ivf_pq_build.cuh:1411-1432), used by the distributed SPMD build where
    a host round-trip is impossible.

    One stable sort of ``labels`` gives each row its (list, slot) address;
    rows with ``labels >= n_lists`` (pad markers) or slot ``>= L``
    (overflow) are dropped by the scatter's ``mode="drop"``.

    Parameters
    ----------
    row_arrays : sequence of [n, ...] arrays to pack per-list.
    labels : [n] int — destination list per row.
    row_ids : [n] int32 — ids stored alongside (global ids for shards).
    n_lists, L : static list count / padded capacity.
    fill_values : pad value per row_array.

    Returns (packed_arrays [n_lists, L, ...], ids [n_lists, L] (-1 pad),
    sizes [n_lists] int32, n_dropped () int32 — rows lost to list
    overflow; callers should surface it, the host packers warn —
    row_addr = (row_list [n], row_slot [n]) int32: each input row's
    packed (list, slot) address; slot >= L marks an overflow-dropped
    row. Returning the addresses here keeps consumers (e.g. CAGRA's
    cluster-blocked graph) from re-deriving the packing order.)

    The stored id table preserves ``row_ids``' policy width
    (``core.ids.id_dtype_like``): an int64 global-id array from a
    ≥ 2³¹-row sharded build packs without a silent int32 truncation.
    """
    from raft_tpu.core import ids as _ids

    n = labels.shape[0]
    labels = labels.astype(jnp.int32)
    order = jnp.argsort(labels, stable=True)
    sorted_l = labels[order]
    starts = jnp.searchsorted(sorted_l, jnp.arange(n_lists, dtype=jnp.int32))
    rank = (jnp.arange(n, dtype=jnp.int32)
            - starts[jnp.clip(sorted_l, 0, n_lists - 1)].astype(jnp.int32))
    packed = []
    for arr, fill in zip(row_arrays, fill_values):
        out = jnp.full((n_lists, L) + arr.shape[1:], fill, arr.dtype)
        packed.append(out.at[sorted_l, rank].set(arr[order], mode="drop"))
    idt = _ids.id_dtype_like(row_ids)
    ids = jnp.full((n_lists, L), -1, idt).at[sorted_l, rank].set(
        row_ids[order].astype(idt), mode="drop")
    counts = jnp.zeros((n_lists,), jnp.int32).at[labels].add(1, mode="drop")
    sizes = jnp.minimum(counts, L)
    n_dropped = jnp.sum(counts - sizes)
    # row-order addresses via the sort's inverse permutation (gathers,
    # not scatters — see segment_probes)
    inv = jnp.argsort(order)
    return packed, ids, sizes, n_dropped, (sorted_l[inv], rank[inv])


pack_lists_jit = partial(jax.jit, static_argnames=("n_lists", "L"))(
    lambda row_arrays, labels, row_ids, n_lists, L, fill_values: pack_lists(
        row_arrays, labels, row_ids, n_lists, L, fill_values))
"""Jitted :func:`pack_lists` — single-program builds (eager packing
costs a host dispatch per op)."""


def pack_rows_chunked(x: jax.Array, labels: jax.Array, n_lists: int,
                      L: int, chunk_rows: int = 1 << 17):
    """Row-chunked device packing of ``x [n, d]`` into ``[n_lists, L,
    d]`` for WIDE datasets — the one-shot :func:`pack_lists` peaks at
    input + full gather copy + padded output (≈ 13.6 GB at 1M×960,
    an OOM on a 16 GB chip). One sort derives every row's flattened
    destination; chunks of rows then gather + scatter into a DONATED
    output buffer, bounding the peak at input + output + one chunk.

    Returns (packed [n_lists, L, d], ids [n_lists, L] (-1 pad),
    sizes [n_lists], n_dropped)."""
    n, d = x.shape
    labels = labels.astype(jnp.int32)

    @partial(jax.jit, static_argnames=("n_lists", "L"))
    def prep(labels, n_lists, L):
        order = jnp.argsort(labels, stable=True)
        sorted_l = labels[order]
        starts = jnp.searchsorted(sorted_l,
                                  jnp.arange(n_lists, dtype=jnp.int32))
        rank = (jnp.arange(n, dtype=jnp.int32)
                - starts[jnp.clip(sorted_l, 0, n_lists - 1)].astype(jnp.int32))
        valid = (sorted_l >= 0) & (sorted_l < n_lists) & (rank < L)
        dest = jnp.where(valid, sorted_l * L + rank, n_lists * L)
        counts = jnp.zeros((n_lists,), jnp.int32).at[
            jnp.clip(labels, 0, n_lists - 1)].add(
                (labels >= 0) & (labels < n_lists), mode="drop")
        return order, dest, jnp.minimum(counts, L), counts

    order, dest, sizes, counts = prep(labels, n_lists, L)

    @partial(jax.jit, donate_argnums=(0, 1))
    def write_chunk(out, ids_out, rows, ridx, dst):
        out = out.at[dst].set(rows, mode="drop")
        ids_out = ids_out.at[dst].set(ridx, mode="drop")
        return out, ids_out

    out = jnp.zeros((n_lists * L, d), x.dtype)
    ids_out = jnp.full((n_lists * L,), -1, jnp.int32)
    for a in range(0, n, chunk_rows):
        b = min(n, a + chunk_rows)
        oc = order[a:b]
        out, ids_out = write_chunk(out, ids_out, x[oc],
                                   oc.astype(jnp.int32), dest[a:b])
    n_dropped = jnp.sum(counts - sizes)
    return (out.reshape(n_lists, L, d), ids_out.reshape(n_lists, L),
            sizes, n_dropped)


def choose_list_chunk(n_lists: int, target: int) -> int:
    """Largest divisor of ``n_lists`` that is ≤ target (chunked scans
    reshape [n_lists, …] to [n_chunks, chunk, …], so the chunk must
    divide n_lists)."""
    c = max(1, min(target, n_lists))
    while n_lists % c:
        c -= 1
    return c
