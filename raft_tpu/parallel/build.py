"""Distributed billion-scale index build — sharded assign+encode with
host→HBM prefetch overlap and allgatherv-lean comms.

The reference's MNMG build story (raft-dask/NCCL: each worker builds
over its slice, SURVEY.md §2.15) restructured for the TPU pod and for
datasets that live in host memmaps rather than device memory — the
missing half of BASELINE config 5 (sharded IVF-PQ, SIFT-1B on v5e-64)
next to PR-8's sharded search. Shape of the pass:

- **coarse + PQ quantizers replicated, trained once** — the SAME
  trainset sample, trainer (:func:`ivf_pq._train_quantizers` /
  ``kmeans_balanced.fit``) and keys as the single-host
  ``build_chunked``, so the distributed build is *bit-identical* to the
  single-host one after assembly (:func:`assemble_ivf_pq`; the CI mesh
  asserts sha equality). The trainset rows are gathered from the shards
  with ONE ``allgatherv`` (each shard contributes the sample rows it
  owns, ragged, packed to rank order); an opt-in ``coarse="distributed"``
  trades the parity guarantee for the psum-Lloyd MNMG trainer
  (:func:`cluster.distributed.fit`) when even the trainset gather is
  too big;
- **assignment + encode shard-parallel over the data axis** — each
  shard walks only its contiguous memmap slice ``[rank·shard_rows,
  …)`` in chunks, with a double-buffered host→HBM prefetcher
  (:class:`ChunkPrefetcher`: a background reader thread issues chunk
  N+1's host read + ``jax.device_put`` under chunk N's jitted
  assign/encode; reads retry under
  :data:`raft_tpu.robust.retry.IO_POLICY` at the ``build.chunk_read``
  fault point). ``build.prefetch.{hit,stall}`` counters and the
  ``span.<entry>.encode`` / ``span.<entry>.h2d`` decomposition prove
  the overlap in obs rows — ``h2d`` times only the *un-hidden* wait;
- **comms stay allgatherv-of-per-list-counts only** — after the train
  phase, the sole collective is one ``allgatherv`` of each shard's
  ``[n_lists]`` label histogram (it sizes the global list capacity
  ``L``); encoded codes, norms and id tables NEVER cross the
  interconnect. Every byte rides the :class:`~raft_tpu.parallel.comms.
  Comms` facade, so ``comms.bytes{op=allgatherv}`` is the build's whole
  comms story (the dryrun asserts exactly that);
- **per-shard output the ring searcher consumes directly** — each shard
  packs its lists host-side in global row order (the
  ``ivf_pq._stable_slots`` pack, cursor-chained across chunks) and the
  stacked result is a :class:`~raft_tpu.parallel.ivf.ShardedIvfPq` /
  ``ShardedIvfFlat`` with global ids stamped via the
  :mod:`raft_tpu.core.ids` policy (``rank·shard_rows + local``,
  int64 past 2³¹ pod rows) — ``search_ivf_pq`` (ring or allgather
  merge, fused scan-in-ring included) takes it as-is;
- **preemption-safe per shard** — with ``checkpoint_dir=`` the PR-7
  checkpoint layer records quantizers, per-shard label passes and one
  encoded shard per (shard, chunk) (``robust.checkpoint`` shard-axis
  naming); resume validates the dataset/params fingerprints
  (fingerprinted ONCE, the elapsed time stamped into the manifest) and
  replays completed chunks to a sha-identical sharded index.

Layout invariant (what makes the sha stable): shard ``s`` packs row
``g`` of list ``l`` at the slot equal to the number of shard-``s`` rows
of list ``l`` preceding ``g`` — so concatenating the shards' list
prefixes in rank order reproduces the single-host pack exactly
(:func:`assemble_ivf_pq`), because shard slices partition the row range
contiguously in rank order.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from raft_tpu.core.errors import expects
from raft_tpu.core.tracing import span
from raft_tpu.core import ids as _ids
from raft_tpu.obs import sanitize as _sanitize
from raft_tpu.obs import spans as _obs_spans
from raft_tpu.parallel.comms import Comms
from raft_tpu.robust import degrade as _degrade
from raft_tpu.robust import faults as _faults
from raft_tpu.robust import retry as _retry
from raft_tpu.utils.precision import get_precision


# ---------------------------------------------------------------------------
# host→HBM chunk prefetcher
# ---------------------------------------------------------------------------

class ChunkPrefetcher:
    """Double-buffered host→HBM chunk pipeline.

    A background reader thread walks ``ranges`` in order, calling
    ``read_fn(lo, hi)`` (host read + dtype convert + ``device_put`` —
    the read retries/faults belong inside ``read_fn``) and parking up to
    ``depth`` finished device chunks in a bounded queue. The consumer's
    :meth:`get` then returns chunk N while the reader is already filling
    chunk N+1 — the host IO and H2D copy of the next chunk hide under
    the current chunk's jitted encode, which runs in XLA-land and
    releases the GIL.

    Accounting (the overlap's proof, recorded only when obs is on):

    - ``build.prefetch.hit{site=}`` — the chunk was already resident
      when requested (the read fully hid under compute);
    - ``build.prefetch.stall{site=}`` — the consumer had to wait; the
      wait itself runs under a ``span("h2d")`` so the *un-hidden*
      host→HBM time lands in ``span.<entry>.h2d`` next to
      ``span.<entry>.encode``.

    ``prefetch=False`` degenerates to a serial reader (every get is an
    inline read under the same span/counter names) — the bench's
    serialized-copy-then-encode comparison leg.

    Error contract: an exception in the reader thread (IO error past the
    retry budget, an injected fault) is re-raised at the consumer's next
    :meth:`get`; the reader exits after queueing it. :meth:`close` is
    idempotent, drains the queue and joins the thread — safe to call
    mid-stream (the ``finally`` of an interrupted build).
    """

    def __init__(self, read_fn: Callable[[int, int], jax.Array],
                 ranges: Sequence[Tuple[int, int]], depth: int = 2,
                 prefetch: bool = True, counter_site: str = "build"):
        self._read = read_fn
        self._ranges = list(ranges)
        self._site = counter_site
        self._prefetch = bool(prefetch) and len(self._ranges) > 0
        self._taken = 0
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        if self._prefetch:
            self._thread = threading.Thread(
                target=self._run, name="raft_tpu-chunk-prefetch",
                daemon=True)
            self._thread.start()

    def __len__(self) -> int:
        return len(self._ranges)

    def _count(self, name: str) -> None:
        if _obs_spans.enabled():
            _obs_spans.registry().inc(name, labels={"site": self._site})

    def _run(self) -> None:
        for i, (a, b) in enumerate(self._ranges):
            if self._stop.is_set():
                return
            try:
                item = (i, self._read(a, b), None)
            except BaseException as e:  # propagated at the next get()
                item = (i, None, e)
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.05)
                    break
                except queue.Full:
                    continue
            if item[2] is not None:
                return

    def get(self) -> jax.Array:
        """Next chunk as a device array (in ``ranges`` order). Raises
        the reader's exception if its read failed; ``IndexError`` past
        the end."""
        if self._taken >= len(self._ranges):
            raise IndexError("ChunkPrefetcher exhausted")
        if not self._prefetch:
            a, b = self._ranges[self._taken]
            self._count("build.prefetch.stall")
            with span("h2d"):
                x = self._read(a, b)
            self._taken += 1
            return x
        # benign race on empty(): a reader mid-put counts as a stall
        # with a ~zero-length wait — the conservative side
        if self._q.empty():
            self._count("build.prefetch.stall")
            with span("h2d"), _sanitize.blocking_region("queue.get"):
                i, x, exc = self._q.get()
        else:
            self._count("build.prefetch.hit")
            with _sanitize.blocking_region("queue.get"):
                i, x, exc = self._q.get()
        if exc is not None:
            self.close()
            raise exc
        self._taken += 1
        return x

    def close(self) -> None:
        """Stop the reader and release queue slots (idempotent). A
        reader stuck inside a slow retried read can outlive the join
        timeout — keep the handle (and say so) instead of dropping the
        reference, so the still-running thread is visible rather than
        silently issuing reads against a stage that moved on."""
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread is not None:
            with _sanitize.blocking_region("join"):
                self._thread.join(timeout=5.0)
            if self._thread.is_alive():
                from raft_tpu.core import logging as _log
                _log.warn("ChunkPrefetcher.close: reader thread still "
                          "inside a read after 5s (slow IO/retry "
                          "backoff) — it will exit at its next "
                          "stop-flag check")
            else:
                self._thread = None


# ---------------------------------------------------------------------------
# shard geometry + the two allgatherv programs
# ---------------------------------------------------------------------------

def shard_ranges(n: int, n_dev: int) -> Tuple[List[Tuple[int, int]], int]:
    """Contiguous per-shard row ranges ``[(lo, hi), ...]`` and the
    padded per-shard row count ``shard_rows = ceil(n / n_dev)`` — the
    global-id offset base (``rank · shard_rows + local``). The last
    shard may be ragged (``hi − lo < shard_rows``)."""
    shard_n = -(-n // n_dev)
    # tail shards past the row count are EMPTY (lo == hi), not negative
    # — a 5-row dataset on an 8-shard mesh builds 3 empty shards
    return ([(min(n, s * shard_n), min(n, (s + 1) * shard_n))
             for s in range(n_dev)], shard_n)


def _chunk_ranges(lo: int, hi: int, chunk_rows: int) -> List[Tuple[int, int]]:
    return [(a, min(hi, a + chunk_rows)) for a in range(lo, hi, chunk_rows)]


def gather_trainset_rows(stacked: jax.Array, counts: jax.Array,
                         n_rows: int, mesh: Mesh, axis: str) -> jax.Array:
    """Replicate the cross-shard trainset with ONE ``allgatherv``.

    ``stacked [n_dev, cap, d]`` holds each shard's owned sample rows
    (ragged, zero-padded to the fattest shard's count), ``counts
    [n_dev]`` the valid-row counts. The allgatherv packs valid rows to
    the front in rank order — and because the global sample indices are
    sorted and shard slices partition the row range contiguously in
    rank order, the packed result IS the sample in global index order:
    bit-equal to the single-host ``dataset[tr_idx]`` read. Counted as
    gather-family traffic on the facade (``comms.bytes{op=allgatherv}``,
    axis-size × payload)."""
    comms = Comms(axis)

    def body(xs, cs):
        g, _ = comms.allgatherv(xs[0], cs[0], compact=True)
        return g

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis, None, None), P(axis)),
                   out_specs=P(), check_vma=False)
    # host-side collective timing (ISSUE 15): the dispatch runs under a
    # comms.allgatherv span (sync mode blocks on the gathered result),
    # so per-host flight dumps carry timed collective events the fleet
    # aggregator's straggler table compares across the pod
    with span("comms.allgatherv", labels={"op": "allgatherv",
                                          "axis": axis}) as sp:
        out = fn(stacked, counts)[:n_rows]
        sp.attach(out)
    return out


def gather_list_counts(local_counts, mesh: Mesh, axis: str) -> jax.Array:
    """The build's ONE post-train collective: every shard's
    ``[n_lists]`` label histogram crosses the interconnect as a single
    ``allgatherv`` row (codes/ids/norms never do) and each shard gets
    the full ``[n_dev, n_lists]`` table back — it sizes the global list
    capacity ``L`` and the stacked per-shard capacity ``L_shard``.
    Returns the gathered (replicated) table; trace-safe, so the
    collective-schedule checker can walk it."""
    comms = Comms(axis)

    def body(c):
        g, _ = comms.allgatherv(c, jnp.int32(1), compact=False)
        return g

    fn = shard_map(body, mesh=mesh, in_specs=(P(axis, None),),
                   out_specs=P(), check_vma=False)
    # timed like gather_trainset_rows: the straggler table wants every
    # host-driven collective dispatch comparable across the pod
    with span("comms.allgatherv", labels={"op": "allgatherv",
                                          "axis": axis}) as sp:
        out = fn(jnp.asarray(local_counts, jnp.int32))
        sp.attach(out)
    return out


# ---------------------------------------------------------------------------
# shared host-side helpers
# ---------------------------------------------------------------------------

def _count_resume(site: str, name: str, value: float = 1.0) -> None:
    if _obs_spans.enabled():
        _obs_spans.registry().inc(name, value, labels={"site": site})


def _read_rows(dataset, idx_or_slice, site: str):
    """One host read under the shared IO retry policy + fault point —
    the same contract as ``build_chunked``'s ``read_chunk``."""
    def _do():
        _faults.faultpoint(site)
        if hasattr(dataset, "sample_rows") and not isinstance(
                idx_or_slice, slice):
            return np.asarray(dataset.sample_rows(idx_or_slice),
                              np.float32)
        return np.asarray(dataset[idx_or_slice], np.float32)
    return _retry.retry_call(_do, site=site, policy=_retry.IO_POLICY)


def _make_read_chunk(dataset, normalize: bool):
    """``read_fn(a, b)`` for the prefetcher: retried host read →
    ``float32`` → device, cosine rows normalized — bit-identical to
    ``build_chunked.to_device(read_chunk(a, b))``."""
    def read_chunk(a, b):
        x = jnp.asarray(_read_rows(dataset, slice(a, b),
                                   "build.chunk_read"))
        if normalize:
            x = x / jnp.sqrt(jnp.maximum(
                jnp.sum(x * x, -1, keepdims=True), 1e-12))
        return x
    return read_chunk


def _owned_sample(dataset, tr_idx: np.ndarray,
                  ranges: Sequence[Tuple[int, int]]
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Each shard's owned sample rows, stacked ragged: ``(stacked
    [n_dev, cap, d] f32 zero-padded, counts [n_dev])``. Reads retry at
    the ``build.train_sample`` fault point."""
    n_dev = len(ranges)
    owned = [tr_idx[(tr_idx >= lo) & (tr_idx < hi)] for lo, hi in ranges]
    cap = max(1, max(len(o) for o in owned))
    d = dataset.shape[1]
    stacked = np.zeros((n_dev, cap, d), np.float32)
    counts = np.zeros((n_dev,), np.int32)
    for s, o in enumerate(owned):
        if len(o):
            stacked[s, :len(o)] = _read_rows(dataset, o,
                                             "build.train_sample")
        counts[s] = len(o)
    return stacked, counts


def _gather_trainset(dataset, tr_idx: np.ndarray,
                     ranges: Sequence[Tuple[int, int]], mesh: Mesh,
                     axis: str, normalize: bool) -> jax.Array:
    """Each shard reads the sample rows it owns (retried at
    ``build.train_sample``), then :func:`gather_trainset_rows`
    replicates them; cosine normalization runs once on the replicated
    result, as the single-host trainer does."""
    stacked, counts = _owned_sample(dataset, tr_idx, ranges)
    tr = gather_trainset_rows(jnp.asarray(stacked), jnp.asarray(counts),
                              len(tr_idx), mesh, axis)
    if normalize:
        tr = tr / jnp.sqrt(jnp.maximum(
            jnp.sum(tr * tr, -1, keepdims=True), 1e-12))
    return tr


def _coarse_distributed(dataset, tr_idx: np.ndarray,
                        ranges: Sequence[Tuple[int, int]], mesh: Mesh,
                        axis: str, n_lists: int, n_iters: int, seed: int,
                        spherical: bool, normalize: bool) -> jax.Array:
    """``coarse="distributed"``'s trainer: psum-Lloyd MNMG kmeans
    (:func:`raft_tpu.cluster.distributed.fit`) over the SHARDED sample —
    each shard's owned rows stay its own slice (the stacked ragged
    sample shards contiguously over the axis; zero weights mask the pad
    rows), so the full trainset is never gathered/replicated: only the
    ``[k, d]`` centroid sums cross the interconnect per Lloyd step.
    This is the mode's reason to exist — the replicated default's
    trainset gather is the thing that stops scaling first."""
    from raft_tpu.cluster import KMeansParams
    from raft_tpu.cluster import distributed as dkm

    n_dev = len(ranges)
    stacked, counts = _owned_sample(dataset, tr_idx, ranges)
    cap = stacked.shape[1]
    x_flat = jnp.asarray(stacked.reshape(n_dev * cap, -1))
    if normalize:
        x_flat = x_flat / jnp.sqrt(jnp.maximum(
            jnp.sum(x_flat * x_flat, -1, keepdims=True), 1e-12))
    w = (np.arange(cap)[None, :] < counts[:, None]).reshape(-1)
    kmp = KMeansParams(n_clusters=n_lists, max_iter=n_iters, seed=seed)
    centers, _, _ = dkm.fit(kmp, x_flat, mesh, axis=axis,
                            weights=jnp.asarray(w, jnp.float32))
    if spherical:
        centers = centers / jnp.sqrt(jnp.maximum(
            jnp.sum(centers ** 2, -1, keepdims=True), 1e-12))
    return centers


def _shard_label_pass(dataset, lo: int, hi: int, chunk_rows: int,
                      predict_fn, prefetch: bool,
                      site: str, normalize: bool) -> np.ndarray:
    """One shard's streaming label pass: chunked walk of the shard's
    memmap slice through the prefetcher, nearest-center assignment per
    chunk under ``span("assign")``."""
    labels = np.empty(hi - lo, np.int32)
    pf = ChunkPrefetcher(_make_read_chunk(dataset, normalize),
                         _chunk_ranges(lo, hi, chunk_rows),
                         prefetch=prefetch, counter_site=site)
    try:
        for a, b in _chunk_ranges(lo, hi, chunk_rows):
            xb = pf.get()
            with span("assign"):
                labels[a - lo:b - lo] = np.asarray(predict_fn(xb))
    finally:
        pf.close()
    return labels


# ---------------------------------------------------------------------------
# IVF-PQ distributed build
# ---------------------------------------------------------------------------

def build_ivf_pq_distributed(dataset, params, mesh: Mesh,
                             axis: str = "shard",
                             chunk_rows: int = 1 << 18,
                             max_train_rows: int = 1 << 21,
                             prefetch: bool = True,
                             coarse: str = "replicated",
                             checkpoint_dir: Optional[str] = None,
                             resume=False,
                             progress: bool = False):
    """Distributed chunked IVF-PQ build (see the module docstring;
    public entry: :func:`raft_tpu.neighbors.ivf_pq.build_distributed`).
    Returns a :class:`~raft_tpu.parallel.ivf.ShardedIvfPq` that
    ``search_ivf_pq`` consumes directly."""
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.distance.types import DistanceType, resolve_metric
    from raft_tpu.neighbors import ivf_pq as _pq
    from raft_tpu.neighbors.ivf_flat import _fit_list_size, _lane_round
    from raft_tpu.parallel.ivf import ShardedIvfPq

    site = "ivf_pq.build_distributed"
    t0 = time.time()

    def _say(msg):
        if progress:
            print(f"[build_distributed +{time.time() - t0:7.0f}s] {msg}",
                  flush=True)

    mt = resolve_metric(params.metric)
    expects(params.codebook_kind == "per_subspace",
            "distributed build supports per_subspace codebooks")
    expects(4 <= params.pq_bits <= 8, "pq_bits must be in [4, 8]")
    expects(not params.spill,
            "distributed build does not support spill=True yet (the "
            "spill cascade needs the global histogram mid-pass)")
    expects(coarse in ("replicated", "distributed"),
            "coarse must be 'replicated' or 'distributed' (got %r)",
            coarse)
    expects(resume in (False, True, "auto"),
            "resume must be False, True, or 'auto' (got %r)", resume)
    expects(not resume or checkpoint_dir is not None,
            "resume=%r needs checkpoint_dir=", resume)
    n, dim = dataset.shape
    n_dev = mesh.shape[axis]
    ranges, shard_n = shard_ranges(n, n_dev)
    spherical = mt in (DistanceType.InnerProduct,
                       DistanceType.CosineExpanded)
    normalize = mt == DistanceType.CosineExpanded

    pq_dim = params.pq_dim or _pq._default_pq_dim(dim)
    pq_len = -(-dim // pq_dim)
    K = 1 << params.pq_bits
    key = jax.random.PRNGKey(params.seed)
    km = KMeansBalancedParams(n_iters=params.kmeans_n_iters,
                              metric="cosine" if spherical else "l2",
                              seed=params.seed)

    # checkpoint bootstrap: fingerprint ONCE (timed), validate on resume
    ck = manifest = None
    base_manifest = {}
    if checkpoint_dir is not None:
        import dataclasses as _dc
        import os

        from raft_tpu.robust import checkpoint as _ckpt

        ck = _ckpt.BuildCheckpoint(checkpoint_dir)
        # fingerprint ONCE for the whole pod build; every shard scope
        # below reuses the pair — shards never re-fingerprint
        ds_sha, p_sha, fp_s = _ckpt.fingerprints_once(
            dataset, {**_dc.asdict(params), "chunk_rows": chunk_rows,
                      "max_train_rows": max_train_rows,
                      "n_shards": n_dev, "coarse": coarse,
                      "build": "distributed"})
        base_manifest = {"dataset_sha": ds_sha, "params_sha": p_sha,
                         "fingerprint_s": round(fp_s, 6),
                         "n": int(n), "dim": int(dim),
                         "chunk_rows": int(chunk_rows),
                         "n_shards": int(n_dev),
                         "shard_rows": int(shard_n)}
        if resume is True or (resume == "auto"
                              and os.path.exists(ck.manifest_path)):
            manifest = ck.load_manifest()
            ck.validate_manifest(manifest, ds_sha, p_sha)
            _count_resume(site, "resume.attempts")
            _say(f"resuming from {ck.manifest_path} "
                 f"(phase {manifest.get('phase')}, shard chunks "
                 f"{manifest.get('shard_chunks_done')})")

    # 1. quantizers — the exact single-host trainer over the exact
    # single-host trainset sample, so the distributed build stays
    # bit-identical to build_chunked after assembly
    if manifest is not None:
        _say("resume: loading quantizer state")
        q = ck.load_arrays("quantizers")
        centers = jnp.asarray(q["centers"])
        rotation = jnp.asarray(q["rotation"])
        centers_rot = jnp.asarray(q["centers_rot"])
        codebooks = jnp.asarray(q["codebooks"])
    else:
        n_train = min(n, max_train_rows,
                      max(params.n_lists * 4, 4 * K,
                          int(n * params.kmeans_trainset_fraction)))
        rng = np.random.default_rng(params.seed)
        tr_idx = np.sort(rng.choice(n, n_train, replace=False))
        with span("train"):
            if coarse == "distributed":
                # the MNMG psum-Lloyd trainer over the SHARDED sample
                # (never replicated — see _coarse_distributed), at the
                # cost of bit-parity with the single-host build
                # (cluster/distributed.py documents the trade). Only
                # the SMALL codebook subsample (the same ≤ 2¹⁶-row
                # stride _train_quantizers would take) is gathered, and
                # the codebooks train on residuals to the DISTRIBUTED
                # centers — the centers the index actually encodes
                # against.
                _say(f"distributed coarse fit over the sharded "
                     f"{n_train}-row sample")
                centers = _coarse_distributed(
                    dataset, tr_idx, ranges, mesh, axis, params.n_lists,
                    params.kmeans_n_iters, params.seed, spherical,
                    normalize)
                stride = max(1, -(-n_train // (1 << 16)))
                cb_sample = _gather_trainset(dataset, tr_idx[::stride],
                                             ranges, mesh, axis,
                                             normalize)
                _, rotation, centers_rot, codebooks = \
                    _pq._train_quantizers(cb_sample, params, dim, pq_dim,
                                          pq_len, K, key, km,
                                          centers=centers)
                del cb_sample
            else:
                _say(f"gathering {n_train} train rows (one allgatherv)")
                trainset = _gather_trainset(dataset, tr_idx, ranges,
                                            mesh, axis, normalize)
                centers, rotation, centers_rot, codebooks = \
                    _pq._train_quantizers(trainset, params, dim, pq_dim,
                                          pq_len, K, key, km)
                del trainset
            jax.block_until_ready(codebooks)
        if ck is not None:
            ck.save_arrays("quantizers",
                           centers=np.asarray(centers),
                           rotation=np.asarray(rotation),
                           centers_rot=np.asarray(centers_rot),
                           codebooks=np.asarray(codebooks))
            ck.write_manifest({**base_manifest, "phase": "label"})
    _say("quantizers trained; per-shard label pass")

    # 2. per-shard streaming label pass (prefetched), then the build's
    # ONE collective: allgatherv of the per-shard label histograms
    have_labels = (manifest is not None
                   and manifest.get("phase") in ("encode", "done"))
    labels_by_shard: List[np.ndarray] = []
    if have_labels:
        _say("resume: loading per-shard label passes")
        for s, (lo, hi) in enumerate(ranges):
            lb = np.asarray(ck.load_arrays(f"labels_s{s:03d}")["labels"],
                            np.int32)
            expects(lb.shape[0] == hi - lo,
                    "resume label checkpoint for shard %d holds %d rows, "
                    "expected %d", s, lb.shape[0], hi - lo)
            labels_by_shard.append(lb)
        # L/L_shard come from the manifest; per-shard sizes re-derive
        # from the loaded labels in the pack loop below
        L = int(manifest["L"])
        L_shard = int(manifest["L_shard"])
    else:
        def predict_fn(xb):
            return kmeans_balanced.predict(centers, xb, km)

        local_counts = np.zeros((n_dev, params.n_lists), np.int64)
        for s, (lo, hi) in enumerate(ranges):
            lb = _shard_label_pass(dataset, lo, hi, chunk_rows,
                                   predict_fn, prefetch, site, normalize)
            labels_by_shard.append(lb)
            local_counts[s] = np.bincount(lb, minlength=params.n_lists)
            if ck is not None:
                ck.save_arrays(f"labels_s{s:03d}", labels=lb)
            _say(f"shard {s}: labeled {hi - lo} rows")
        counts_by_shard = np.asarray(
            gather_list_counts(local_counts, mesh, axis))
        counts = counts_by_shard.sum(axis=0)
        avg = max(1, n // params.n_lists)
        L = _fit_list_size(counts, avg, params.list_size_cap_factor)
        # the stacked per-shard capacity: big enough that no shard drops
        # a row the GLOBAL capacity would keep (a kept row's within-
        # shard slot is < min(L, its shard's fattest list)), small
        # enough that the [n_dev, n_lists, L_shard, ...] tables don't
        # pay the global capacity per shard
        L_shard = min(L, _lane_round(int(max(1, counts_by_shard.max()))))
        if ck is not None:
            ck.write_manifest({**base_manifest, "phase": "encode",
                               "L": int(L), "L_shard": int(L_shard),
                               "shard_chunks_done": [0] * n_dev})
    nbytes = _pq.packed_nbytes(pq_dim, params.pq_bits)
    n_total_pad = n_dev * shard_n  # id width follows the PADDED total
    id_dt = _ids.np_id_dtype(n_total_pad)

    # 3. per-shard encode + pack (prefetched; codes never leave the
    # shard). RESOURCE_EXHAUSTED on an encode chunk halves it in place —
    # each row's encode is independent.
    def encode_rows(xb, lb, lo, hi):
        try:
            codes, norms = _pq._encode_with_norms(
                jnp.matmul(xb, rotation.T, precision=get_precision()),
                centers_rot, lb, codebooks,
                params.codebook_kind)
            return (_pq.pack_bits_np(np.asarray(codes), params.pq_bits),
                    np.asarray(norms))
        except Exception as e:
            if not _degrade.is_resource_exhausted(e) or hi - lo <= 1024:
                raise
            _degrade.note_step(site, "chunk", "half_chunk",
                               "resource_exhausted")
            mid = (hi - lo) // 2
            c1, n1 = encode_rows(xb[:mid], lb[:mid], lo, lo + mid)
            c2, n2 = encode_rows(xb[mid:], lb[mid:], lo + mid, hi)
            return np.concatenate([c1, c2]), np.concatenate([n1, n2])

    chunks_done = (list(manifest.get("shard_chunks_done", [0] * n_dev))
                   if have_labels else [0] * n_dev)
    packed = np.zeros((n_dev, params.n_lists, L_shard, nbytes), np.uint8)
    ids = np.full((n_dev, params.n_lists, L_shard), -1, id_dt)
    pnorm = np.zeros((n_dev, params.n_lists, L_shard), np.float32)
    sizes = np.zeros((n_dev, params.n_lists), np.int32)
    dropped = 0
    with span("encode_pack"):
        for s, (lo, hi) in enumerate(ranges):
            labels_s = labels_by_shard[s]
            cursor = np.zeros(params.n_lists, np.int64)
            chunks = _chunk_ranges(lo, hi, chunk_rows)
            pf = ChunkPrefetcher(
                _make_read_chunk(dataset, normalize),
                # replayed chunks need no device work — don't read them
                chunks[chunks_done[s]:], prefetch=prefetch,
                counter_site=site)
            try:
                for ci, (a, b) in enumerate(chunks):
                    if ci < chunks_done[s]:
                        shard = ck.load_shard(ci, shard=s)
                        codes_h = np.asarray(shard["codes"], np.uint8)
                        norms_h = np.asarray(shard["norms"], np.float32)
                        expects(codes_h.shape[0] == b - a,
                                "resume shard (%d, chunk %d) holds %d "
                                "rows, expected %d — corrupt checkpoint",
                                s, ci, codes_h.shape[0], b - a)
                        _count_resume(site, "resume.chunks_replayed")
                    else:
                        xb = pf.get()
                        _faults.faultpoint("build.chunk_encode")
                        lb = jnp.asarray(labels_s[a - lo:b - lo])
                        with span("encode"):
                            codes_h, norms_h = encode_rows(xb, lb, a, b)
                        if ck is not None:
                            # shard first, then the manifest recording
                            # it (the build_chunked ordering)
                            ck.save_shard(ci, shard=s, codes=codes_h,
                                          norms=norms_h)
                            done = list(chunks_done)
                            done[s] = ci + 1
                            ck.write_manifest(
                                {**base_manifest, "phase": "encode",
                                 "L": int(L), "L_shard": int(L_shard),
                                 "shard_chunks_done": done})
                            chunks_done = done
                    lb_h = labels_s[a - lo:b - lo]
                    order, sorted_l, slot = _pq._stable_slots(
                        lb_h, params.n_lists, cursor)
                    keep = (slot < L_shard) & (sorted_l < params.n_lists)
                    dropped += int((~keep).sum())
                    rows = order[keep]
                    ls, sl = sorted_l[keep], slot[keep].astype(np.int64)
                    packed[s, ls, sl] = codes_h[rows]
                    # global ids through the one id-dtype policy:
                    # rank·shard_rows + local (= the global row number,
                    # because shard slices are contiguous in rank order)
                    ids[s, ls, sl] = (a + rows).astype(id_dt)
                    pnorm[s, ls, sl] = norms_h[rows]
                    cursor = np.minimum(
                        cursor + np.bincount(
                            lb_h, minlength=params.n_lists), L_shard)
            finally:
                pf.close()
            sizes[s] = np.minimum(
                np.bincount(labels_s, minlength=params.n_lists),
                L_shard).astype(np.int32)
            _say(f"shard {s}: encoded rows [{lo}, {hi})")
    if ck is not None:
        ck.write_manifest({**base_manifest, "phase": "done",
                           "L": int(L), "L_shard": int(L_shard),
                           "shard_chunks_done":
                               [len(_chunk_ranges(lo, hi, chunk_rows))
                                for lo, hi in ranges]})
    if dropped:
        from raft_tpu.core import logging as _log
        _log.warn("distributed ivf_pq build: dropped %d overflow vectors "
                  "(raise list_size_cap_factor)", dropped)
        from raft_tpu.neighbors.ivf_common import note_rows_dropped

        note_rows_dropped("ivf_pq", dropped)
    return ShardedIvfPq(
        centers=centers, centers_rot=centers_rot, rotation=rotation,
        codebooks=codebooks, packed_codes=jnp.asarray(packed),
        packed_ids=jnp.asarray(ids), packed_norms=jnp.asarray(pnorm),
        list_sizes=jnp.asarray(sizes), metric=mt.value,
        pq_bits=params.pq_bits, pq_dim=pq_dim, shard_rows=shard_n,
        global_list_cap=int(L))


# ---------------------------------------------------------------------------
# IVF-Flat distributed build (the twin: raw rows instead of codes)
# ---------------------------------------------------------------------------

def build_ivf_flat_distributed(dataset, params, mesh: Mesh,
                               axis: str = "shard",
                               chunk_rows: int = 1 << 18,
                               max_train_rows: int = 1 << 21,
                               prefetch: bool = True,
                               coarse: str = "replicated",
                               progress: bool = False):
    """Distributed chunked IVF-Flat build — the raw-vector twin of
    :func:`build_ivf_pq_distributed` (public entry:
    ``ivf_flat.build_distributed``). Same shard walk and allgatherv-lean
    comms; the per-chunk "encode" is just the row norms, and each shard
    packs its raw f32 rows. Assembly parity with the single-host
    ``ivf_flat.build`` holds while the trainset stays under
    ``max_train_rows`` (the single-host build has no cap)."""
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.distance.types import DistanceType, resolve_metric
    from raft_tpu.neighbors import ivf_pq as _pq
    from raft_tpu.neighbors.ivf_flat import _fit_list_size, _lane_round
    from raft_tpu.parallel.ivf import ShardedIvfFlat

    site = "ivf_flat.build_distributed"
    t0 = time.time()

    def _say(msg):
        if progress:
            print(f"[build_distributed +{time.time() - t0:7.0f}s] {msg}",
                  flush=True)

    mt = resolve_metric(params.metric)
    expects(not params.spill,
            "distributed build does not support spill=True yet")
    expects(coarse in ("replicated", "distributed"),
            "coarse must be 'replicated' or 'distributed' (got %r)",
            coarse)
    n, dim = dataset.shape
    expects(params.n_lists <= n, "n_lists=%d > n=%d", params.n_lists, n)
    n_dev = mesh.shape[axis]
    ranges, shard_n = shard_ranges(n, n_dev)
    spherical = mt in (DistanceType.InnerProduct,
                       DistanceType.CosineExpanded)
    km = KMeansBalancedParams(n_iters=params.kmeans_n_iters,
                              metric="cosine" if spherical else "l2",
                              seed=params.seed)

    # 1. coarse centers: the exact single-host trainset + trainer
    # (ivf_flat.build's formula) over the allgatherv'd sample
    n_train = min(n, max_train_rows,
                  max(params.n_lists * 4,
                      int(n * params.kmeans_trainset_fraction)))
    rng = np.random.default_rng(params.seed)
    tr_idx = (np.sort(rng.choice(n, n_train, replace=False))
              if n_train < n else np.arange(n))
    with span("train"):
        if coarse == "distributed":
            # sharded psum-Lloyd sample, never replicated (see
            # _coarse_distributed); parity with ivf_flat.build waived
            _say(f"distributed coarse fit over the sharded "
                 f"{n_train}-row sample")
            centers = _coarse_distributed(
                dataset, tr_idx, ranges, mesh, axis, params.n_lists,
                params.kmeans_n_iters, params.seed, spherical,
                normalize=False)
        else:
            _say(f"gathering {n_train} train rows (one allgatherv)")
            trainset = _gather_trainset(dataset, tr_idx, ranges, mesh,
                                        axis, normalize=False)
            centers = kmeans_balanced.fit(trainset, params.n_lists, km)
            del trainset
        jax.block_until_ready(centers)
    _say("coarse centers trained; per-shard label pass")

    # 2. per-shard label pass + the one per-list-count allgatherv
    def predict_fn(xb):
        return kmeans_balanced.predict(centers, xb, km)

    labels_by_shard = []
    local_counts = np.zeros((n_dev, params.n_lists), np.int64)
    for s, (lo, hi) in enumerate(ranges):
        lb = _shard_label_pass(dataset, lo, hi, chunk_rows, predict_fn,
                               prefetch, site, normalize=False)
        labels_by_shard.append(lb)
        local_counts[s] = np.bincount(lb, minlength=params.n_lists)
        _say(f"shard {s}: labeled {hi - lo} rows")
    counts_by_shard = np.asarray(
        gather_list_counts(local_counts, mesh, axis))
    counts = counts_by_shard.sum(axis=0)
    avg = max(1, n // params.n_lists)
    L = _fit_list_size(counts, avg, params.list_size_cap_factor)
    L_shard = min(L, _lane_round(int(max(1, counts_by_shard.max()))))
    n_total_pad = n_dev * shard_n
    id_dt = _ids.np_id_dtype(n_total_pad)

    # 3. per-shard pack of raw rows (prefetched walk; rows never cross).
    # This pass is HOST-ONLY — the labels are already computed, the pack
    # is a host scatter — so the prefetcher's read_fn skips the device
    # round-trip a device chunk would pay for nothing: the reader thread
    # overlaps the raw memmap read (retried at build.chunk_read) under
    # the consumer's host pack of the previous chunk.
    def read_rows_host(a, b):
        return _read_rows(dataset, slice(a, b), "build.chunk_read")

    packed = np.zeros((n_dev, params.n_lists, L_shard, dim), np.float32)
    ids = np.full((n_dev, params.n_lists, L_shard), -1, id_dt)
    sizes = np.zeros((n_dev, params.n_lists), np.int32)
    dropped = 0
    with span("encode_pack"):
        for s, (lo, hi) in enumerate(ranges):
            labels_s = labels_by_shard[s]
            cursor = np.zeros(params.n_lists, np.int64)
            pf = ChunkPrefetcher(read_rows_host,
                                 _chunk_ranges(lo, hi, chunk_rows),
                                 prefetch=prefetch, counter_site=site)
            try:
                for a, b in _chunk_ranges(lo, hi, chunk_rows):
                    rows_h = pf.get()
                    lb_h = labels_s[a - lo:b - lo]
                    order, sorted_l, slot = _pq._stable_slots(
                        lb_h, params.n_lists, cursor)
                    keep = (slot < L_shard) & (sorted_l < params.n_lists)
                    dropped += int((~keep).sum())
                    rows = order[keep]
                    ls, sl = sorted_l[keep], slot[keep].astype(np.int64)
                    packed[s, ls, sl] = rows_h[rows]
                    ids[s, ls, sl] = (a + rows).astype(id_dt)
                    cursor = np.minimum(
                        cursor + np.bincount(
                            lb_h, minlength=params.n_lists), L_shard)
            finally:
                pf.close()
            sizes[s] = np.minimum(
                np.bincount(labels_s, minlength=params.n_lists),
                L_shard).astype(np.int32)
            _say(f"shard {s}: packed rows [{lo}, {hi})")
    if dropped:
        from raft_tpu.core import logging as _log
        _log.warn("distributed ivf_flat build: dropped %d overflow "
                  "vectors (raise list_size_cap_factor)", dropped)
        from raft_tpu.neighbors.ivf_common import note_rows_dropped

        note_rows_dropped("ivf_flat", dropped)
    packed_j = jnp.asarray(packed)
    # norms from the PACKED table (pad slots 0) with the same reduction
    # shape as the single-host build — bit-parity by construction
    norms = jnp.sum(packed_j * packed_j, axis=-1)
    return ShardedIvfFlat(centers=centers, packed_data=packed_j,
                          packed_ids=jnp.asarray(ids),
                          packed_norms=norms,
                          list_sizes=jnp.asarray(sizes),
                          metric=mt.value, global_list_cap=int(L))


# ---------------------------------------------------------------------------
# assembly — the sha-identity bridge to the single-host builders
# ---------------------------------------------------------------------------

def _assemble_lists(sizes: np.ndarray, L_shard: int, L: int):
    """Slot plan for concatenating per-shard list prefixes in rank
    order: returns ``(shard, list, src_slot, dst_slot)`` index arrays,
    truncated at the global capacity ``L`` (the rows a single-host pack
    would have dropped)."""
    n_dev, n_lists = sizes.shape
    base = np.zeros((n_dev, n_lists), np.int64)
    np.cumsum(sizes[:-1], axis=0, out=base[1:])
    slot = np.arange(L_shard)[None, None, :]
    valid = slot < sizes[:, :, None]
    dst = base[:, :, None] + slot
    keep = valid & (dst < L)
    sh, li, src = np.nonzero(keep)
    return sh, li, src, dst[keep]


def assemble_ivf_pq(sharded, cache_reconstruction: str = "never"):
    """Merge a distributed-built :class:`ShardedIvfPq` into the
    single-host :class:`~raft_tpu.neighbors.ivf_pq.IvfPqIndex` —
    bit-identical to ``build_chunked`` over the same dataset/params
    (the layout invariant in the module docstring; the CI mesh asserts
    the sha). Useful when a pod build feeds a single-chip serving
    host."""
    from raft_tpu.neighbors import ivf_pq as _pq

    expects(sharded.global_list_cap > 0,
            "assemble needs a distributed-built index (global_list_cap "
            "is unset on hand-assembled shards)")
    L = int(sharded.global_list_cap)
    sizes = np.asarray(sharded.list_sizes)
    n_dev, n_lists, L_shard = np.asarray(sharded.packed_ids).shape
    nb = np.asarray(sharded.packed_codes).shape[-1]
    sh, li, src, dst = _assemble_lists(sizes, L_shard, L)
    s_codes = np.asarray(sharded.packed_codes)
    s_ids = np.asarray(sharded.packed_ids)
    s_norms = np.asarray(sharded.packed_norms)
    packed = np.zeros((n_lists, L, nb), np.uint8)
    ids = np.full((n_lists, L), -1, _ids.np_id_dtype_like(s_ids))
    pnorm = np.zeros((n_lists, L), np.float32)
    packed[li, dst] = s_codes[sh, li, src]
    ids[li, dst] = s_ids[sh, li, src]
    pnorm[li, dst] = s_norms[sh, li, src]
    list_sizes = np.minimum(sizes.sum(axis=0), L).astype(np.int32)
    # the single-host builder's lane-fold policy, reproduced
    fold = (nb < 128 and packed.nbytes > (1 << 30) and (L * nb) % 128 == 0)
    if fold:
        packed = packed.reshape(n_lists, -1, 128)
    index = _pq.IvfPqIndex(
        centers=sharded.centers, centers_rot=sharded.centers_rot,
        rotation=sharded.rotation, codebooks=sharded.codebooks,
        packed_codes=jnp.asarray(packed), packed_ids=jnp.asarray(ids),
        packed_norms=jnp.asarray(pnorm),
        list_sizes=jnp.asarray(list_sizes), metric=sharded.metric,
        codebook_kind="per_subspace", pq_bits=sharded.pq_bits,
        pq_dim_static=sharded.pq_dim, codes_folded=fold)
    if cache_reconstruction == "always":
        index = index.replace(packed_recon=_pq._build_recon_cache(index))
    return index


def assemble_ivf_flat(sharded):
    """Merge a distributed-built ``ShardedIvfFlat`` into the single-host
    :class:`~raft_tpu.neighbors.ivf_flat.IvfFlatIndex` (bit-identical to
    ``ivf_flat.build`` over the same dataset/params)."""
    from raft_tpu.neighbors import ivf_flat as _flat

    expects(sharded.global_list_cap > 0,
            "assemble needs a distributed-built index (global_list_cap "
            "is unset on hand-assembled shards)")
    L = int(sharded.global_list_cap)
    sizes = np.asarray(sharded.list_sizes)
    n_dev, n_lists, L_shard = np.asarray(sharded.packed_ids).shape
    d = np.asarray(sharded.packed_data).shape[-1]
    sh, li, src, dst = _assemble_lists(sizes, L_shard, L)
    s_data = np.asarray(sharded.packed_data)
    s_ids = np.asarray(sharded.packed_ids)
    packed = np.zeros((n_lists, L, d), s_data.dtype)
    ids = np.full((n_lists, L), -1, _ids.np_id_dtype_like(s_ids))
    packed[li, dst] = s_data[sh, li, src]
    ids[li, dst] = s_ids[sh, li, src]
    list_sizes = np.minimum(sizes.sum(axis=0), L).astype(np.int32)
    packed_j = jnp.asarray(packed)
    return _flat.IvfFlatIndex(
        centers=sharded.centers, packed_data=packed_j,
        packed_ids=jnp.asarray(ids),
        packed_norms=jnp.sum(packed_j.astype(jnp.float32) ** 2, axis=-1),
        list_sizes=jnp.asarray(list_sizes), metric=sharded.metric)


def index_sha16(index) -> str:
    """16-hex content sha over an index's arrays (field-name order) —
    the identity the chaos lane and the dryrun's distributed-vs-
    single-host assertion both hash."""
    import hashlib

    h = hashlib.sha256()
    for name in sorted(f.name for f in index.__dataclass_fields__.values()
                       if f.metadata.get("pytree_node", True)):
        v = getattr(index, name)
        if v is None:
            continue
        h.update(name.encode())
        h.update(np.ascontiguousarray(np.asarray(v)).tobytes())
    return h.hexdigest()[:16]
