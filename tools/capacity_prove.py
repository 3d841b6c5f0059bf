"""Billion-scale capacity proofs — the CI gate over the public entries.

Device-free (``JAX_PLATFORMS=cpu``, ``jax.eval_shape`` semantics — the
synthetic SIFT-1B-scale operands are ``jax.ShapeDtypeStruct``, zero
bytes allocated): every proof traces a public search/build entry at
n ≥ 2³¹ synthetic shapes and runs
:func:`raft_tpu.obs.sanitize.assert_billion_safe` over the jaxpr — the
runtime half of graftlint's capacity pass (GL11–GL15), and the TPU
counterpart of the reference templating every index on a 64-bit
``IdxT``.

Each proof ends by **addressing the dataset with the returned ids**
(one marker-row gather): an id path that silently narrowed to int32
anywhere upstream surfaces here as an int32 gather into a ≥ 2³¹ axis,
even when the narrowing site itself never indexes.

Proof set (the acceptance list from ISSUE 10):

- ``ivf_pq`` / ``ivf_flat`` / ``brute_force`` / ``cagra`` search
- the FILTERED ``ivf_pq`` search incl. the fused tiers' packed-byte
  operand prep (ISSUE 12 — the bitset word-index divide must run in
  the incoming id width)
- the sharded cross-shard merge tier (ring + allgather, global-id
  remap included) on the 8-device CPU mesh
- ``build_chunked``'s assignment/encode pass at the LAST chunk's row
  offset (where the ``a + row`` global-id stamp is largest)
- the DISTRIBUTED build's per-shard assignment/encode pass on the
  8-device mesh (ISSUE 13): the ``rank·shard_rows + local`` global-id
  stamp plus the per-list-count allgatherv
- the tiered refine's device epilogue (ISSUE 17):
  ``refine.refine_landed`` over prefetched candidate rows with int64
  candidate ids into a ≥ 2³¹ host row axis

Run: ``JAX_PLATFORMS=cpu python -m tools.capacity_prove [--n N]
[--report PATH]`` — exit 0 when every proof is clean, 1 with the
violating eqns otherwise.
"""

from __future__ import annotations

import json
import os
import sys

# the merge proof needs the 8-device CPU mesh; set before the first
# jax import (conftest does the same for the test suite)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xla_flags:
    os.environ["XLA_FLAGS"] = (
        _xla_flags + " --xla_force_host_platform_device_count=8").strip()

# SIFT-1B-and-change: comfortably past 2³¹ so int32 id paths cannot hide
DEFAULT_N = 2_200_000_000
_DIM = 8        # feature width is irrelevant to id capacity; keep traces small
_K = 4
_M = 4          # queries


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _address_rows(marker, gids):
    """The canonical end-of-proof step: returned ids must ADDRESS the
    dataset. ``marker`` is an abstract [n, 1] int8 stand-in for the row
    store; an id path that narrowed to int32 upstream becomes an int32
    gather into the ≥ 2³¹ row axis right here."""
    import jax.numpy as jnp

    return marker[jnp.where(gids >= 0, gids, 0)]


def prove_brute_force(n: int = DEFAULT_N) -> dict:
    import jax.numpy as jnp
    from raft_tpu.neighbors import brute_force
    from raft_tpu.obs import sanitize as _san

    def fn(ds, q, marker):
        idx = brute_force.build(ds, metric="sqeuclidean")
        vals, ids = brute_force.knn(idx, q, _K)
        return vals, ids, _address_rows(marker, ids)

    return _san.assert_billion_safe(
        fn, _sds((n, _DIM), jnp.float32), _sds((_M, _DIM), jnp.float32),
        _sds((n, 1), jnp.int8), what="brute_force.knn")


def _abstract_ivf_pq(n: int):
    import jax.numpy as jnp
    from raft_tpu.core import ids as _ids
    from raft_tpu.neighbors import ivf_pq as _pq

    n_lists = 64
    L = -(-n // n_lists)
    L = -(-L // 8) * 8
    pq_dim, pq_bits = _DIM, 8
    nbytes = _pq.packed_nbytes(pq_dim, pq_bits)
    idt = _ids.id_dtype(n)
    index = _pq.IvfPqIndex(
        centers=_sds((n_lists, _DIM), jnp.float32),
        centers_rot=_sds((n_lists, _DIM), jnp.float32),
        rotation=_sds((_DIM, _DIM), jnp.float32),
        codebooks=_sds((pq_dim, 1 << pq_bits, 1), jnp.float32),
        packed_codes=_sds((n_lists, L, nbytes), jnp.uint8),
        packed_ids=_sds((n_lists, L), idt),
        packed_norms=_sds((n_lists, L), jnp.float32),
        list_sizes=_sds((n_lists,), jnp.int32),
        metric="sqeuclidean", pq_bits=pq_bits, pq_dim_static=pq_dim)
    return index


def prove_ivf_pq(n: int = DEFAULT_N) -> dict:
    import jax.numpy as jnp
    from raft_tpu.neighbors import ivf_pq as _pq
    from raft_tpu.obs import sanitize as _san

    index = _abstract_ivf_pq(n)
    params = _pq.SearchParams(n_probes=2, scan_mode="per_query")

    def fn(index, q, marker):
        vals, ids = _pq.search(index, q, _K, params)
        return vals, ids, _address_rows(marker, ids)

    return _san.assert_billion_safe(
        fn, index, _sds((_M, _DIM), jnp.float32), _sds((n, 1), jnp.int8),
        what="ivf_pq.search")


def prove_filtered_search(n: int = DEFAULT_N) -> dict:
    """ISSUE 12: the FILTERED search path at n = 2.2e9 — the packed
    bitset has ceil(n/32) uint32 words, and every word-index divide
    (``bitset.word_at``'s ``ids // WORD_BITS``, reached through
    ``sample_filter.passes`` on the scan path and
    ``sample_filter.list_filter_bytes`` in the fused tiers' host-side
    operand prep) must run in the INCOMING int64 id width — an int32
    narrowing anywhere upstream becomes an int32 gather into the
    ≥ 2³¹-word axis right here (GL11's runtime half)."""
    import jax.numpy as jnp
    from raft_tpu.neighbors import ivf_pq as _pq
    from raft_tpu.neighbors import sample_filter as _sf
    from raft_tpu.obs import sanitize as _san

    index = _abstract_ivf_pq(n)
    params = _pq.SearchParams(n_probes=2, scan_mode="per_query")
    n_words = -(-n // 32)

    def fn(index, q, bits, marker):
        vals, ids = _pq.search(index, q, _K, params, filter_bitset=bits)
        # the fused tiers' operand prep over the full id table: one
        # passes() gather + byte re-pack per list (the [n_lists,
        # ceil(L/8)] stream the kernels DMA per code tile)
        fbytes = _sf.list_filter_bytes(bits, index.packed_ids)
        return vals, ids, fbytes, _address_rows(marker, ids)

    return _san.assert_billion_safe(
        fn, index, _sds((_M, _DIM), jnp.float32),
        _sds((n_words,), jnp.uint32), _sds((n, 1), jnp.int8),
        what="ivf_pq.search[filtered]")


def prove_ivf_flat(n: int = DEFAULT_N) -> dict:
    import jax.numpy as jnp
    from raft_tpu.core import ids as _ids
    from raft_tpu.neighbors import ivf_flat as _flat
    from raft_tpu.obs import sanitize as _san

    n_lists = 64
    L = -(-(-(-n // n_lists)) // 8) * 8
    idt = _ids.id_dtype(n)
    index = _flat.IvfFlatIndex(
        centers=_sds((n_lists, _DIM), jnp.float32),
        packed_data=_sds((n_lists, L, _DIM), jnp.float32),
        packed_ids=_sds((n_lists, L), idt),
        packed_norms=_sds((n_lists, L), jnp.float32),
        list_sizes=_sds((n_lists,), jnp.int32),
        metric="sqeuclidean")
    params = _flat.SearchParams(n_probes=2, scan_mode="per_query")

    def fn(index, q, marker):
        vals, ids = _flat.search(index, q, _K, params)
        return vals, ids, _address_rows(marker, ids)

    return _san.assert_billion_safe(
        fn, index, _sds((_M, _DIM), jnp.float32), _sds((n, 1), jnp.int8),
        what="ivf_flat.search")


def prove_cagra(n: int = DEFAULT_N) -> dict:
    import jax.numpy as jnp
    from raft_tpu.core import ids as _ids
    from raft_tpu.neighbors import cagra as _cagra
    from raft_tpu.obs import sanitize as _san

    idt = _ids.id_dtype(n)
    index = _cagra.CagraIndex(
        dataset=_sds((n, _DIM), jnp.float32),
        graph=_sds((n, 8), idt), metric="sqeuclidean")
    params = _cagra.SearchParams(itopk_size=32, search_width=2,
                                 num_seeds=128, max_iterations=2)

    def fn(index, q, marker):
        vals, ids = _cagra.search(index, q, _K, params)
        return vals, ids, _address_rows(marker, ids)

    return _san.assert_billion_safe(
        fn, index, _sds((_M, _DIM), jnp.float32), _sds((n, 1), jnp.int8),
        what="cagra.search")


def prove_sharded_merge(n: int = DEFAULT_N, tier: str = "ring") -> dict:
    """The cross-shard merge tier at pod scale: per-shard local top-k
    tables remapped to global ids (``core.ids.global_ids`` — the
    rank·shard_rows offset is the int32-overflow site), merged through
    ``parallel.merge.merge_topk``, merged ids addressing the global row
    axis. Runs on the 8-device CPU mesh (ring tier = the
    identical-schedule ppermute fallback; the int32-only Pallas kernel
    is TPU-gated and declined for int64 ids by ``merge_topk``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from raft_tpu.core import ids as _ids
    from raft_tpu.obs import sanitize as _san
    from raft_tpu.parallel import merge as _merge
    from raft_tpu.parallel.comms import Comms

    n_dev = 8
    shard_rows = -(-n // n_dev)
    devices = jax.devices()[:n_dev]
    mesh = Mesh(np.array(devices), ("shard",))
    comms = Comms("shard")
    impl = "ring_ppermute" if tier == "ring" else "allgather"

    def local(vals, lids, marker):
        rank = comms.get_rank()
        gids = _ids.global_ids(rank, shard_rows, lids, n_total=n)
        rv, ri = _merge.merge_topk(vals, gids, "shard", _M, _K, n_dev,
                                   True, tier=tier, impl=impl)
        return rv, ri, _address_rows(marker, ri)

    out = _merge.merge_out_spec(tier, "shard")
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(), P(), P()),
                   out_specs=(out, out, out), check_vma=False)

    lid_dt = _ids.id_dtype(shard_rows)
    return _san.assert_billion_safe(
        fn, _sds((_M, _K), jnp.float32), _sds((_M, _K), lid_dt),
        _sds((n, 1), jnp.int8), what=f"parallel.merge[{tier}]")


def prove_build_chunked_pass(n: int = DEFAULT_N,
                             chunk: int = 1 << 14) -> dict:
    """``build_chunked``'s assignment/encode pass at the LAST chunk's
    offset: coarse assignment, residual encode, and the global-id stamp
    ``a + row`` (``core.ids.make_ids(chunk, start=a)``) — the site the
    host packer routes through ``np_id_dtype`` and the device twin must
    keep wide."""
    import jax.numpy as jnp
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
    from raft_tpu.core import ids as _ids
    from raft_tpu.neighbors import ivf_pq as _pq
    from raft_tpu.obs import sanitize as _san

    n_lists = 64
    a = (n // chunk) * chunk - chunk  # final full chunk's row offset
    km = KMeansBalancedParams(metric="l2")

    def fn(xb, centers, centers_rot, rotation, codebooks, marker):
        labels = kmeans_balanced.predict(centers, xb, km)
        codes, norms = _pq._encode_with_norms(
            xb @ rotation.T, centers_rot,
            jnp.clip(labels, 0, n_lists - 1), codebooks, "per_subspace")
        gids = _ids.make_ids(chunk, start=a, n_total=n)
        return codes, norms, gids, _address_rows(marker, gids)

    return _san.assert_billion_safe(
        fn, _sds((chunk, _DIM), jnp.float32),
        _sds((n_lists, _DIM), jnp.float32),
        _sds((n_lists, _DIM), jnp.float32),
        _sds((_DIM, _DIM), jnp.float32),
        _sds((_DIM, 256, 1), jnp.float32),
        _sds((n, 1), jnp.int8),
        what="ivf_pq.build_chunked[assign+encode]")


def prove_build_distributed_pass(n: int = DEFAULT_N,
                                 chunk: int = 1 << 14) -> dict:
    """The DISTRIBUTED build's per-shard assignment+encode pass at the
    LAST chunk's offset on the 8-device mesh (ISSUE 13): coarse
    assignment, residual encode, the global-id stamp through
    ``core.ids.global_ids`` (``rank · shard_rows + local`` — the int32
    overflow site the moment the pod holds ≥ 2³¹ rows), and the build's
    one collective, the allgatherv of per-list counts. Ends by
    addressing the global row axis with the stamped ids, so an upstream
    int32 narrowing surfaces as an int32 gather into the ≥ 2³¹ axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
    from jax import shard_map
    from raft_tpu.core import ids as _ids
    from raft_tpu.neighbors import ivf_pq as _pq
    from raft_tpu.obs import sanitize as _san
    from raft_tpu.parallel.comms import Comms

    n_dev = 8
    n_lists = 64
    shard_rows = -(-n // n_dev)
    a = (shard_rows // chunk) * chunk - chunk  # last full in-shard chunk
    devices = jax.devices()[:n_dev]
    mesh = Mesh(np.array(devices), ("shard",))
    comms = Comms("shard")
    km = KMeansBalancedParams(metric="l2")

    def local(xb, centers, centers_rot, rotation, codebooks, marker):
        rank = comms.get_rank()
        labels = kmeans_balanced.predict(centers, xb, km)
        codes, norms = _pq._encode_with_norms(
            xb @ rotation.T, centers_rot,
            jnp.clip(labels, 0, n_lists - 1), codebooks, "per_subspace")
        # the build's one post-train collective: per-list counts only
        counts = jax.ops.segment_sum(jnp.ones((chunk,), jnp.float32),
                                     labels, num_segments=n_lists)
        g, _ = comms.allgatherv(counts[None], jnp.int32(1),
                                compact=False)
        gids = _ids.global_ids(rank, shard_rows,
                               _ids.make_ids(chunk, start=a,
                                             n_total=n_dev * shard_rows),
                               n_total=n_dev * shard_rows)
        return codes, norms, g, gids, _address_rows(marker, gids)

    out = (P(), P(), P(), P(), P())
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(), P(), P(), P(), P(), P()),
                   out_specs=out, check_vma=False)
    return _san.assert_billion_safe(
        fn, _sds((chunk, _DIM), jnp.float32),
        _sds((n_lists, _DIM), jnp.float32),
        _sds((n_lists, _DIM), jnp.float32),
        _sds((_DIM, _DIM), jnp.float32),
        _sds((_DIM, 256, 1), jnp.float32),
        _sds((n, 1), jnp.int8),
        what="ivf_pq.build_distributed[assign+encode]")


def prove_tiered_refine(n: int = DEFAULT_N) -> dict:
    """ISSUE 17: the memory-tiered refined search's DEVICE half at
    billion scale — candidate ids arrive from the oversampled scan in
    the wide id dtype, the exact re-rank runs on already-landed
    prefetched rows (``refine.refine_landed`` → the shared
    ``_refine_rows`` program), and the returned ids must still address
    the ≥ 2³¹-row host base. The host gather itself is numpy (clip +
    fancy-index — 64-bit by construction); this proves the jitted
    epilogue never narrows the id path."""
    import jax.numpy as jnp
    from raft_tpu.core import ids as _ids
    from raft_tpu.neighbors import refine as _refine
    from raft_tpu.obs import sanitize as _san

    C = 16
    idt = _ids.id_dtype(n)

    def fn(rows, q, cand, marker):
        vals, ids = _refine.refine_landed(rows, q, cand, _K)
        return vals, ids, _address_rows(marker, ids)

    return _san.assert_billion_safe(
        fn, _sds((_M, C, _DIM), jnp.float32),
        _sds((_M, _DIM), jnp.float32), _sds((_M, C), idt),
        _sds((n, 1), jnp.int8), what="refine.refine_landed[tiered]")


PROOFS = {
    "brute_force.knn": prove_brute_force,
    "ivf_pq.search": prove_ivf_pq,
    "ivf_pq.search_filtered": prove_filtered_search,
    "ivf_flat.search": prove_ivf_flat,
    "cagra.search": prove_cagra,
    "merge.ring": lambda n=DEFAULT_N: prove_sharded_merge(n, "ring"),
    "merge.allgather": lambda n=DEFAULT_N: prove_sharded_merge(
        n, "allgather"),
    "build_chunked.assign_encode": prove_build_chunked_pass,
    "build_distributed.assign_encode": prove_build_distributed_pass,
    "tiered.refine_landed": prove_tiered_refine,
}


def run_all(n: int = DEFAULT_N) -> dict:
    """Run every proof; returns {name: report}. Raises CapacityError on
    the first violating entry (tests call individual proofs instead)."""
    return {name: proof(n) for name, proof in PROOFS.items()}


def main(argv=None) -> int:
    import argparse

    from raft_tpu.obs.sanitize import CapacityError

    ap = argparse.ArgumentParser(
        prog="capacity_prove",
        description="eval_shape capacity proofs over the public entries "
                    "at billion-scale synthetic shapes (device-free)")
    ap.add_argument("--n", type=int, default=DEFAULT_N,
                    help=f"synthetic row count (default {DEFAULT_N})")
    ap.add_argument("--report", metavar="PATH", default=None,
                    help="write a JSON report (per-proof verdicts) — the "
                         "CI artifact")
    ap.add_argument("--only", default=None,
                    help="comma-separated proof names (default: all)")
    args = ap.parse_args(argv)

    names = list(PROOFS)
    if args.only:
        names = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = set(names) - set(PROOFS)
        if unknown:
            print(f"capacity_prove: unknown proof(s): {sorted(unknown)}",
                  file=sys.stderr)
            return 2
    results = {}
    failed = False
    for name in names:
        try:
            rep = PROOFS[name](args.n)
            results[name] = {"ok": True,
                             "peak_intermediate_bytes":
                                 rep["peak_intermediate_bytes"]}
            print(f"  PASS {name}  (peak intermediate "
                  f"{rep['peak_intermediate_bytes'] / 2**30:.1f} GiB)")
        except CapacityError as e:
            failed = True
            results[name] = {"ok": False, "error": str(e)}
            print(f"  FAIL {name}\n{e}")
    doc = {"version": "raft_tpu.capacity_prove/1", "n": args.n,
           "proofs": results}
    if args.report:
        with open(args.report, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2)
    print("capacity_prove: " + ("VIOLATIONS FOUND" if failed else
                                f"all {len(names)} proofs clean at "
                                f"n={args.n:,}"))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
