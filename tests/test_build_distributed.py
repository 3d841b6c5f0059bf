"""Distributed billion-scale build (ISSUE 13) on the 8-device CPU mesh.

The load-bearing claim is BIT-IDENTITY: the sharded assign+encode pass
(each shard walking only its slice, different chunk shapes, different
walk order) must assemble into exactly the index the single-host
``build_chunked`` produces — quantizers, packed codes, ids, norms,
sizes, byte for byte. Plus: the prefetcher's accounting/shutdown/error
contracts, the allgatherv-only comms story, the collective-schedule
checker over the build's two collectives, and per-shard checkpointed
resume. The heaviest parity variants are slow-marked (PR-10/12
precedent); the core pq8 + flat parities stay tier-1.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.neighbors import ivf_flat, ivf_pq
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.parallel import (
    ChunkPrefetcher,
    assemble_ivf_flat,
    assemble_ivf_pq,
    build_ivf_pq_distributed,
    index_sha16,
    make_mesh,
    search_ivf_pq,
)
from raft_tpu.parallel import build as dbuild
from raft_tpu.robust import faults

CHUNK = 100


@pytest.fixture(autouse=True)
def _clean():
    faults.clear_plan()
    yield
    faults.clear_plan()
    obs.disable()


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    # NOT divisible by 8 and not by CHUNK: ragged last shard AND a
    # ragged final chunk inside every shard walk
    return rng.random((1043, 16), dtype=np.float32)


def _pq_params(**kw):
    kw.setdefault("n_lists", 8)
    kw.setdefault("pq_dim", 8)
    kw.setdefault("kmeans_n_iters", 4)
    kw.setdefault("seed", 0)
    kw.setdefault("cache_reconstruction", "never")
    return ivf_pq.IndexParams(**kw)


def _assert_identical(a, b):
    for name in ("centers", "centers_rot", "rotation", "codebooks",
                 "packed_codes", "packed_ids", "packed_norms",
                 "list_sizes"):
        if not hasattr(a, name):
            continue
        fa, fb = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert fa.dtype == fb.dtype, name
        assert np.array_equal(fa, fb), name


class TestDistributedBuildParity:
    @pytest.mark.slow  # the flat bit-identical twin stays tier-1; CI distributed legs run this one (tier-1 budget)
    def test_ivf_pq_bit_identical_to_build_chunked(self, mesh, data):
        """The acceptance bar: 8-shard distributed build, assembled,
        equals the single-host build_chunked byte for byte — even with
        DIFFERENT chunk sizes (chunk boundaries are not part of the
        result)."""
        params = _pq_params()
        sharded = ivf_pq.build_distributed(data, params, mesh=mesh,
                                           chunk_rows=CHUNK)
        single = ivf_pq.build_chunked(data, params, chunk_rows=4 * CHUNK)
        asm = assemble_ivf_pq(sharded)
        _assert_identical(asm, single)
        assert index_sha16(asm) == index_sha16(single)
        # the sharded layout invariant: global ids carry the shard
        # offset (rank·shard_rows + local), every stored id owned by
        # its shard's contiguous slice
        ids = np.asarray(sharded.packed_ids)
        sr = sharded.shard_rows
        for s in range(sharded.n_shards):
            own = ids[s][ids[s] >= 0]
            assert own.size and (own // sr == s).all()

    @pytest.mark.slow  # second full pq build pair; CI lanes run it
    def test_pq4_parity(self, mesh, data):
        params = _pq_params(pq_bits=4, seed=2)
        sharded = ivf_pq.build_distributed(data, params, mesh=mesh,
                                           chunk_rows=CHUNK)
        single = ivf_pq.build_chunked(data, params, chunk_rows=CHUNK)
        assert index_sha16(assemble_ivf_pq(sharded)) == \
            index_sha16(single)

    @pytest.mark.slow  # cosine normalization path; CI lanes run it
    def test_cosine_parity(self, mesh, data):
        params = _pq_params(metric="cosine", seed=3)
        sharded = ivf_pq.build_distributed(data, params, mesh=mesh,
                                           chunk_rows=CHUNK)
        single = ivf_pq.build_chunked(data, params, chunk_rows=4 * CHUNK)
        assert index_sha16(assemble_ivf_pq(sharded)) == \
            index_sha16(single)

    def test_ivf_flat_bit_identical_to_build(self, mesh, data):
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4,
                                      seed=1)
        sharded = ivf_flat.build_distributed(data, params, mesh=mesh,
                                             chunk_rows=CHUNK)
        single = ivf_flat.build(jnp.asarray(data), params)
        asm = assemble_ivf_flat(sharded)
        _assert_identical(asm, single)
        assert index_sha16(asm) == index_sha16(single)

    def test_ivf_flat_uncapped_bit_identical_to_build(self, mesh, data):
        """No list cap: the distributed build takes None as the
        single-host build does (capacity the largest list, every row
        kept)."""
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4, seed=1,
                                      list_size_cap_factor=None)
        sharded = ivf_flat.build_distributed(data, params, mesh=mesh,
                                             chunk_rows=CHUNK)
        single = ivf_flat.build(jnp.asarray(data), params)
        _assert_identical(assemble_ivf_flat(sharded), single)
        assert single.size == data.shape[0]
        assert single.max_list_size == ivf_flat._lane_round(
            int(np.asarray(single.list_sizes).max()))

    def test_search_consumes_per_shard_output_directly(self, mesh, data):
        """ISSUE 13 (c): the per-shard output IS a ShardedIvfPq — the
        PR-8 searcher takes it with no conversion, through both the
        parallel entry and the neighbors pod dispatch, and returns
        valid global ids."""
        params = _pq_params()
        sharded = ivf_pq.build_distributed(data, params, mesh=mesh,
                                           chunk_rows=CHUNK)
        q = jnp.asarray(data[:16])
        sp = ivf_pq.SearchParams(n_probes=8)
        vals, ids = search_ivf_pq(sp, sharded, q, 5, mesh)
        ids = np.asarray(ids)
        assert ids.shape == (16, 5) and (ids >= 0).any()
        assert ids.max() < len(data)
        # self-queries find themselves through the pod dispatch
        _, ids2 = ivf_pq.search(sharded, q, 1, sp, mesh=mesh)
        assert (np.asarray(ids2)[:, 0] == np.arange(16)).mean() >= 0.8

    @pytest.mark.slow  # own distributed build for a refusal path; CI lanes run it (tier-1 budget)
    def test_assemble_refuses_unknown_capacity(self, mesh, data):
        from raft_tpu.parallel import build_ivf_pq as spmd_build

        params = _pq_params()
        # the SPMD device-resident builder doesn't stamp the global
        # capacity — assembly cannot reproduce a single-host pack
        sharded = spmd_build(params, jnp.asarray(data[:512]), mesh)
        with pytest.raises(Exception, match="global_list_cap"):
            assemble_ivf_pq(sharded)

    def test_spill_not_supported(self, mesh, data):
        with pytest.raises(Exception, match="spill"):
            ivf_pq.build_distributed(data, _pq_params(spill=True),
                                     mesh=mesh)


class TestChunkPrefetcher:
    """The prefetcher's contracts: hit/stall accounting, reader-thread
    exception propagation, clean shutdown mid-stream."""

    def _counters(self, reg):
        return {k: v for k, v in reg.snapshot()["counters"].items()
                if k.startswith("build.prefetch.")}

    def test_hit_and_stall_accounting(self):
        import time

        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        pf = ChunkPrefetcher(lambda a, b: np.arange(a, b),
                             [(0, 4), (4, 8), (8, 12)],
                             counter_site="t")
        try:
            # first get may stall (the reader just started); give the
            # reader time to park the rest -> hits
            first = pf.get()
            time.sleep(0.3)
            rest = [pf.get(), pf.get()]
        finally:
            pf.close()
            obs.disable()
        assert np.array_equal(first, np.arange(0, 4))
        assert np.array_equal(rest[1], np.arange(8, 12))
        c = self._counters(reg)
        assert c.get("build.prefetch.hit{site=t}", 0) >= 2
        total = sum(c.values())
        assert total == 3  # every get counted exactly once

    def test_serial_mode_counts_stalls_only(self):
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        pf = ChunkPrefetcher(lambda a, b: np.arange(a, b),
                             [(0, 2), (2, 4)], prefetch=False,
                             counter_site="t")
        try:
            pf.get(), pf.get()
        finally:
            pf.close()
            obs.disable()
        c = self._counters(reg)
        assert c == {"build.prefetch.stall{site=t}": 2.0}

    def test_reader_exception_propagates(self):
        def boom(a, b):
            if a >= 2:
                raise IOError("disk gone")
            return np.arange(a, b)

        pf = ChunkPrefetcher(boom, [(0, 2), (2, 4), (4, 6)])
        try:
            assert np.array_equal(pf.get(), np.arange(0, 2))
            with pytest.raises(IOError, match="disk gone"):
                pf.get()
                pf.get()
        finally:
            pf.close()

    def test_exhausted_raises(self):
        pf = ChunkPrefetcher(lambda a, b: np.arange(a, b), [(0, 1)])
        try:
            pf.get()
            with pytest.raises(IndexError):
                pf.get()
        finally:
            pf.close()

    def test_clean_shutdown_mid_stream(self):
        import threading

        n_before = threading.active_count()
        pf = ChunkPrefetcher(lambda a, b: np.zeros(b - a),
                             [(i, i + 1) for i in range(64)], depth=2)
        pf.get()
        pf.close()
        pf.close()  # idempotent
        assert pf._thread is None
        assert threading.active_count() <= n_before + 1

    def test_faulted_read_retries_under_io_policy(self):
        """An injected IO error on a chunk read recovers under
        IO_POLICY and counts retry.recovered{site=build.chunk_read} —
        the chaos contract, exercised at the prefetcher level."""
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        faults.install_plan({"faults": [
            {"site": "build.chunk_read", "kind": "error", "times": 1}]})
        rng_data = np.arange(40, dtype=np.float32).reshape(10, 4)
        read = dbuild._make_read_chunk(rng_data, normalize=False)
        pf = ChunkPrefetcher(read, [(0, 5), (5, 10)])
        try:
            a, b = np.asarray(pf.get()), np.asarray(pf.get())
        finally:
            pf.close()
            faults.clear_plan()
            obs.disable()
        assert np.array_equal(np.concatenate([a, b]), rng_data)
        c = reg.snapshot()["counters"]
        assert c.get("retry.recovered{site=build.chunk_read}", 0) == 1


class TestBuildComms:
    """ISSUE 13 (c): the build's collective story is allgatherv-only —
    one trainset gather, one per-list-count gather; codes/ids/norms
    never cross the interconnect."""

    def test_allgatherv_only_and_counts(self, mesh, data):
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            ivf_pq.build_distributed(data, _pq_params(), mesh=mesh,
                                     chunk_rows=CHUNK)
        finally:
            obs.disable()
        c = reg.snapshot()["counters"]
        comm = {k: v for k, v in c.items() if k.startswith("comms.")}
        assert comm, "build recorded no collective traffic"
        assert all("op=allgatherv" in k for k in comm), comm
        # exactly two collectives: trainset rows + per-list counts
        assert comm.get("comms.ops{axis=shard,op=allgatherv}") == 2.0
        # prefetch accounting rode along
        assert any(k.startswith("build.prefetch.") for k in c), c

    def test_collective_schedule_uniform(self, mesh):
        """Both build collectives pass the runtime collective-schedule
        checker, with the facade recorder attributing the allgatherv
        verbs (the GL10 completeness pair)."""
        from raft_tpu.obs import sanitize

        counts = np.tile(np.arange(8, dtype=np.int64), (8, 1))
        stacked = jnp.zeros((8, 4, 8), jnp.float32)
        ns = jnp.full((8,), 4, jnp.int32)
        with sanitize.record_comms_schedule() as rec:
            sanitize.assert_uniform_collective_schedule(
                lambda: dbuild.gather_list_counts(counts, mesh, "shard"))
            sanitize.assert_uniform_collective_schedule(
                lambda: dbuild.gather_trainset_rows(stacked, ns, 32,
                                                    mesh, "shard"))
        verbs = [v for v, _, _ in rec]
        assert verbs == ["allgatherv", "allgatherv"], rec
        assert all(a == "shard" for _, a, _ in rec)


class TestDistributedResume:
    """Per-shard checkpointed resume (the PR-7 layer grown a shard
    axis): an interrupted pod build replays to a sha-identical sharded
    index, with resume.* counters and the once-computed fingerprint
    stamped in the manifest."""

    @pytest.mark.slow  # three full distributed builds; CI lanes run it
    def test_interrupted_then_resumed_is_identical(self, mesh, data,
                                                   tmp_path):
        params = _pq_params()
        faults.install_plan({"faults": [
            {"site": "build.chunk_encode", "kind": "error",
             "after": 6}]})
        with pytest.raises(faults.FaultInjected):
            ivf_pq.build_distributed(data, params, mesh=mesh,
                                     chunk_rows=CHUNK,
                                     checkpoint_dir=str(tmp_path))
        faults.clear_plan()
        man = json.load(open(tmp_path / "manifest.json"))
        assert man["phase"] == "encode"
        assert man["n_shards"] == 8 and man["shard_rows"] == 131
        assert man["fingerprint_s"] >= 0
        done = man["shard_chunks_done"]
        assert len(done) == 8 and 0 < sum(done) < 8 * 2
        # the shard-axis file layout: s000_shard_000000.npz etc.
        assert any(f.startswith("s000_shard_") for f in
                   os.listdir(tmp_path))
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        resumed = ivf_pq.build_distributed(data, params, mesh=mesh,
                                           chunk_rows=CHUNK,
                                           checkpoint_dir=str(tmp_path),
                                           resume=True)
        obs.disable()
        clean = ivf_pq.build_distributed(data, params, mesh=mesh,
                                         chunk_rows=CHUNK)
        assert index_sha16(resumed) == index_sha16(clean)
        c = reg.snapshot()["counters"]
        site = "{site=ivf_pq.build_distributed}"
        assert c[f"resume.attempts{site}"] == 1.0
        assert c[f"resume.chunks_replayed{site}"] == sum(done)

    def test_wrong_dataset_refuses(self, mesh, data, tmp_path):
        params = _pq_params()
        # die on the first encode chunk — the manifest is already on
        # disk, and the refusal matrix doesn't need a complete build
        faults.install_plan({"faults": [
            {"site": "build.chunk_encode", "kind": "error",
             "after": 1}]})
        with pytest.raises(faults.FaultInjected):
            ivf_pq.build_distributed(data, params, mesh=mesh,
                                     chunk_rows=CHUNK,
                                     checkpoint_dir=str(tmp_path))
        faults.clear_plan()
        other = np.random.default_rng(99).random(data.shape,
                                                 dtype=np.float32)
        with pytest.raises(Exception, match="different dataset"):
            ivf_pq.build_distributed(other, params, mesh=mesh,
                                     chunk_rows=CHUNK,
                                     checkpoint_dir=str(tmp_path),
                                     resume=True)

    def test_resume_needs_checkpoint_dir(self, mesh, data):
        with pytest.raises(Exception, match="checkpoint_dir"):
            ivf_pq.build_distributed(data, _pq_params(), mesh=mesh,
                                     resume=True)


class TestDistributedCoarseMode:
    """coarse='distributed' routes the coarse trainer through the
    psum-Lloyd MNMG path (cluster.distributed.fit) — sha-parity is
    waived, the index must still search."""

    @pytest.mark.slow  # an extra full build; CI lanes run it
    def test_distributed_coarse_searches(self, mesh, data):
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            sharded = ivf_pq.build_distributed(
                data, _pq_params(), mesh=mesh, chunk_rows=CHUNK,
                coarse="distributed")
        finally:
            obs.disable()
        # the mode's reason to exist: the coarse fit rode the psum
        # Lloyd (allreduce traffic), the full sample was never
        # allgatherv'd — only the small codebook subsample was
        c = reg.snapshot()["counters"]
        assert c.get("comms.ops{axis=shard,op=allreduce}", 0) > 0, c
        # the codebooks must be trained against the DISTRIBUTED
        # centers: self-queries quantize well enough to find
        # themselves (a center/codebook mismatch tanks this)
        q = jnp.asarray(data[:16])
        _, ids = search_ivf_pq(ivf_pq.SearchParams(n_probes=8), sharded,
                               q, 3, mesh)
        ids = np.asarray(ids)
        assert ids.max() < len(data)
        assert (ids[:, 0] == np.arange(16)).mean() >= 0.7

    def test_bad_coarse_mode_rejected(self, mesh, data):
        with pytest.raises(Exception, match="coarse"):
            build_ivf_pq_distributed(data, _pq_params(), mesh,
                                     coarse="nope")
