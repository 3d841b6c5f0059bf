"""Ring reduce-scatter-of-top-k merge tier (ISSUE 8) on the virtual
8-device CPU mesh: the Pallas kernel's interpret-mode remote-DMA ring
vs numpy, the ppermute fallback's parity with the allgather tier,
exact per-hop ``comms.ops/bytes{op=ring_topk}`` accounting, and
collective-schedule uniformity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from raft_tpu.obs import sanitize
from raft_tpu.ops import pallas_kernels as pk
from raft_tpu.parallel import (
    Comms,
    make_mesh,
    merge_out_spec,
    merge_tier,
    merge_topk,
    merged_rows,
    sharded_knn,
)
from raft_tpu.parallel.merge import ring_auto_wanted

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(axis_names=("shard",))


def numpy_merge(vals, ids, k, select_min):
    """Reference merge: per query, stable top-k over every device's
    candidates (ids < 0 are invalid regardless of their key)."""
    n_dev, m, _ = vals.shape
    cat_v = np.concatenate([vals[d] for d in range(n_dev)], axis=1)
    cat_i = np.concatenate([ids[d] for d in range(n_dev)], axis=1)
    key = np.where(cat_i < 0, np.inf, cat_v if select_min else -cat_v)
    order = np.argsort(key, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(cat_v, order, 1),
            np.take_along_axis(cat_i, order, 1))


def make_tables(rng, m, k, select_min, dup_ids=False, sentinels=False):
    """Per-device local top-k tables, sorted the way a local search
    emits them (ascending keys for min-select, descending for max)."""
    vals = rng.random((N_DEV, m, k)).astype(np.float32)
    ids = rng.integers(0, 100_000, (N_DEV, m, k)).astype(np.int32)
    if dup_ids:  # the same candidate surviving twice is kept twice
        ids[:, :, 1] = ids[:, :, 0]
    order = np.argsort(vals if select_min else -vals, axis=-1)
    vals = np.take_along_axis(vals, order, -1)
    ids = np.take_along_axis(ids, order, -1)
    if sentinels:  # short tables pad their tails with ±inf sentinels
        pad = np.inf if select_min else -np.inf
        vals[2, :, -2:] = pad
        ids[2, :, -2:] = -1
        vals[5, :, :] = pad  # a whole shard with no candidates
        ids[5, :, :] = -1
    return vals, ids


class TestRingKernelParity:
    """The ACTUAL Pallas kernel (remote DMAs run by the interpreter
    across the 8 CPU devices) vs numpy."""

    def _run_kernel(self, mesh, vals, ids, k, select_min):
        m = vals.shape[1]

        def body(v, i):
            return pk.ring_topk_merge(v[0], i[0], k, "shard", N_DEV,
                                      select_min, interpret=True)

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P("shard", None, None), P("shard", None, None)),
            out_specs=(P("shard", None), P("shard", None)),
            check_vma=False)
        gv, gi = fn(jnp.asarray(vals), jnp.asarray(ids))
        return np.asarray(gv)[:m], np.asarray(gi)[:m]

    @pytest.mark.slow  # k1/max_select keep kernel parity tier-1 (tier-1 budget)
    def test_ragged_m_min_select(self, mesh, rng):
        # m=27: chunks pad to 8 sublane rows, pad rows must not leak
        vals, ids = make_tables(rng, 27, 10, True)
        gv, gi = self._run_kernel(mesh, vals, ids, 10, True)
        rv, ri = numpy_merge(vals, ids, 10, True)
        np.testing.assert_array_equal(gv, rv)
        np.testing.assert_array_equal(gi, ri)

    def test_max_select(self, mesh, rng):
        # ip-style keys: bigger is better, −inf sentinels
        vals, ids = make_tables(rng, 16, 4, False, sentinels=True)
        gv, gi = self._run_kernel(mesh, vals, ids, 4, False)
        rv, ri = numpy_merge(vals, ids, 4, False)
        np.testing.assert_array_equal(gv, rv)
        np.testing.assert_array_equal(gi, ri)

    @pytest.mark.slow  # k1/max_select keep kernel parity tier-1 (tier-1 budget)
    def test_duplicate_ids_and_sentinels(self, mesh, rng):
        vals, ids = make_tables(rng, 8, 6, True, dup_ids=True,
                                sentinels=True)
        gv, gi = self._run_kernel(mesh, vals, ids, 6, True)
        rv, ri = numpy_merge(vals, ids, 6, True)
        np.testing.assert_array_equal(gv, rv)
        np.testing.assert_array_equal(gi, ri)

    def test_k1(self, mesh, rng):
        vals, ids = make_tables(rng, 9, 1, True)
        gv, gi = self._run_kernel(mesh, vals, ids, 1, True)
        rv, ri = numpy_merge(vals, ids, 1, True)
        np.testing.assert_array_equal(gv, rv)
        np.testing.assert_array_equal(gi, ri)

    def test_kernel_guards(self):
        with pytest.raises(ValueError, match="extraction rounds"):
            pk.ring_topk_merge(jnp.zeros((8, 128)),
                               jnp.zeros((8, 128), jnp.int32),
                               pk.RING_TOPK_MAX_K + 1, "shard", 8)
        assert not pk.ring_topk_kernel_ok(64, pk.RING_TOPK_MAX_K + 1, 8)
        assert not pk.ring_topk_kernel_ok(64, 8, 1)
        assert pk.ring_topk_kernel_ok(64, 8, 8)


class TestRingOverlapSchedule:
    """ISSUE 11 tentpole: the compute/comms-overlapped (half-pipelined)
    hop schedule is exact-parity with the PR-8 serialized schedule —
    kernel-vs-numpy across both schedules at shapes where the overlap
    actually splits (mc ≥ 16), plus the split/env plumbing."""

    def _run_kernel(self, mesh, vals, ids, k, select_min, schedule):
        m = vals.shape[1]

        def body(v, i):
            return pk.ring_topk_merge(v[0], i[0], k, "shard", N_DEV,
                                      select_min, interpret=True,
                                      schedule=schedule)

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P("shard", None, None), P("shard", None, None)),
            out_specs=(P("shard", None), P("shard", None)),
            check_vma=False)
        gv, gi = fn(jnp.asarray(vals), jnp.asarray(ids))
        return np.asarray(gv)[:m], np.asarray(gi)[:m]

    # the serial leg re-proves the PR-8 schedule (already covered by
    # TestRingKernelParity) — slow lane; the overlap leg stays tier-1
    @pytest.mark.parametrize("schedule", [
        pytest.param("serial", marks=pytest.mark.slow), "overlap"])
    def test_two_half_parity_min_select(self, mesh, rng, schedule):
        # m=200 → mc=32 → the overlap schedule really splits (16+16)
        vals, ids = make_tables(rng, 200, 10, True, dup_ids=True)
        gv, gi = self._run_kernel(mesh, vals, ids, 10, True, schedule)
        rv, ri = numpy_merge(vals, ids, 10, True)
        np.testing.assert_array_equal(gv, rv)
        np.testing.assert_array_equal(gi, ri)

    @pytest.mark.parametrize("schedule", ["serial", "overlap"])
    @pytest.mark.slow  # heavy interpret-mode kernel traces; CI lanes run it
    def test_uneven_halves_max_select(self, mesh, rng, schedule):
        # m=129 → mc=24 → uneven (8, 16) halves; −inf sentinels ride
        vals, ids = make_tables(rng, 129, 6, False, sentinels=True)
        gv, gi = self._run_kernel(mesh, vals, ids, 6, False, schedule)
        rv, ri = numpy_merge(vals, ids, 6, False)
        np.testing.assert_array_equal(gv, rv)
        np.testing.assert_array_equal(gi, ri)

    @pytest.mark.slow  # k=64 extraction rounds x 7 hops x 2 schedules
    def test_overlap_matches_serial(self, mesh, rng):
        vals, ids = make_tables(rng, 256, pk.RING_TOPK_MAX_K, True,
                                sentinels=True)
        so = self._run_kernel(mesh, vals, ids, pk.RING_TOPK_MAX_K, True,
                              "overlap")
        ss = self._run_kernel(mesh, vals, ids, pk.RING_TOPK_MAX_K, True,
                              "serial")
        np.testing.assert_array_equal(so[0], ss[0])
        np.testing.assert_array_equal(so[1], ss[1])

    def test_splits(self):
        # serial: one block; overlap: two sublane-aligned halves that
        # tile the chunk exactly (the byte model is rows-preserving)
        assert pk.ring_topk_splits(32, "serial") == ((0, 32),)
        assert pk.ring_topk_splits(32, "overlap") == ((0, 16), (16, 16))
        assert pk.ring_topk_splits(24, "overlap") == ((0, 8), (8, 16))
        # chunks too short to split degenerate to one block
        assert pk.ring_topk_splits(8, "overlap") == ((0, 8),)
        for mc in (8, 16, 24, 32, 104):
            for sched in ("serial", "overlap"):
                splits = pk.ring_topk_splits(mc, sched)
                assert sum(r for _, r in splits) == mc
                assert all(r % 8 == 0 and o % 8 == 0 for o, r in splits)

    def test_schedule_env(self, monkeypatch):
        assert pk.ring_schedule("serial") == "serial"
        assert pk.ring_schedule("overlap") == "overlap"
        monkeypatch.setenv("RAFT_TPU_RING_OVERLAP", "off")
        assert pk.ring_schedule("auto") == "serial"
        monkeypatch.setenv("RAFT_TPU_RING_OVERLAP", "on")
        assert pk.ring_schedule("auto") == "overlap"
        monkeypatch.delenv("RAFT_TPU_RING_OVERLAP")
        assert pk.ring_schedule("auto") == "overlap"  # the default

    def test_overlap_schedule_uniform_and_counted(self, mesh, rng,
                                                  monkeypatch):
        # the overlapped kernel under the collective-schedule checker +
        # facade hop accounting: byte model identical to serial
        monkeypatch.setenv("RAFT_TPU_RING_OVERLAP", "on")
        x = jnp.asarray(rng.random((2048, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((256, 16), dtype=np.float32))
        with sanitize.record_comms_schedule() as rec:
            sanitize.assert_uniform_collective_schedule(
                lambda: sharded_knn(x, q, 4, mesh, merge="ring"))
        hops = [e for e in rec if e[0] == "ring_topk"]
        assert len(hops) == N_DEV - 1, rec
        mc = pk.ring_chunk_rows(256, N_DEV)
        assert all(b == mc * 4 * 8 for _, _, b in hops), rec


class TestRingFallbackParity:
    """The ppermute fallback inside real sharded searches: identical
    results to the allgather tier (same candidates, same selection)."""

    @pytest.mark.slow  # impl-twin parity; the CI ring smoke + pytest lane re-assert it (tier-1 budget)
    def test_sharded_knn_ring_matches_allgather(self, mesh, rng):
        x = jnp.asarray(rng.random((803, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((27, 16), dtype=np.float32))
        va, ia = sharded_knn(x, q, 10, mesh, merge="allgather")
        vr, ir = sharded_knn(x, q, 10, mesh, merge="ring")
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ir))
        np.testing.assert_allclose(np.asarray(va), np.asarray(vr))

    @pytest.mark.slow  # one more full sharded trace; CI lanes run it
    def test_sharded_knn_ring_inner_product(self, mesh, rng):
        # max-select end to end (negated keys through the ring)
        x = jnp.asarray(rng.random((256, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((16, 16), dtype=np.float32))
        va, ia = sharded_knn(x, q, 5, mesh, metric="inner_product",
                             merge="allgather")
        vr, ir = sharded_knn(x, q, 5, mesh, metric="inner_product",
                             merge="ring")
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ir))
        np.testing.assert_allclose(np.asarray(va), np.asarray(vr))

    @pytest.mark.slow  # two full impl traces; CI lanes run it
    def test_kernel_impl_matches_fallback(self, mesh, rng):
        # the merge_topk dispatch's two ring impls agree hop for hop
        m, k = 40, 8
        vals, ids = make_tables(rng, m, k, True)

        def run(impl):
            def body(v, i):
                return merge_topk(v[0], i[0], "shard", m, k, N_DEV, True,
                                  tier="ring", impl=impl, interpret=True)

            fn = shard_map(
                body, mesh=mesh,
                in_specs=(P("shard", None, None), P("shard", None, None)),
                out_specs=(P("shard", None), P("shard", None)),
                check_vma=False)
            gv, gi = fn(jnp.asarray(vals), jnp.asarray(ids))
            return np.asarray(gv)[:m], np.asarray(gi)[:m]

        kv, ki = run("ring_kernel")
        fv, fi = run("ring_ppermute")
        np.testing.assert_array_equal(ki, fi)
        np.testing.assert_allclose(kv, fv)

    def test_int64_ids_decline_the_kernel(self, mesh, rng):
        """The id-width admission (PR-10 capacity pass): the ring
        KERNEL is int32-only by construction, so an int64 billion-scale
        id table must reroute to the identical-schedule ppermute
        fallback (fallback{reason=id_width}) instead of silently
        truncating. Trace-only under scoped x64 — the branch is a
        trace-time dtype check."""
        from raft_tpu import obs
        from raft_tpu.obs.metrics import MetricsRegistry

        m, k = 40, 8
        vals, ids = make_tables(rng, m, k, True)

        def body(v, i):
            return merge_topk(v[0], i[0].astype(jnp.int64), "shard", m,
                              k, N_DEV, True, tier="ring",
                              impl="ring_kernel", interpret=True)

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P("shard", None, None), P("shard", None, None)),
            out_specs=(P("shard", None), P("shard", None)),
            check_vma=False)
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            with sanitize.scoped_x64(True):
                closed = jax.make_jaxpr(fn)(jnp.asarray(vals),
                                            jnp.asarray(ids))
        finally:
            obs.disable()
        c = reg.snapshot()["counters"]
        assert c.get("parallel.merge.fallback{reason=id_width}") == 1.0
        # merged ids keep their 64-bit width end to end
        assert "int64" in str(closed.jaxpr.outvars[1].aval)


class TestMergeTierDispatch:
    def test_env_tristate(self, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_RING_TOPK", "off")
        assert merge_tier(8, 64, 10)[0] == "allgather"
        monkeypatch.setenv("RAFT_TPU_RING_TOPK", "on")
        tier, impl = merge_tier(8, 64, 10)
        assert tier == "ring"
        assert impl == "ring_ppermute"  # CPU: the kernel needs a TPU
        monkeypatch.setenv("RAFT_TPU_RING_TOPK", "auto")
        assert merge_tier(8, 64, 10)[0] == "allgather"  # auto off-TPU

    def test_explicit_overrides_env(self, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_RING_TOPK", "off")
        assert merge_tier(8, 64, 10, explicit="ring")[0] == "ring"
        with pytest.raises(Exception, match="merge tier"):
            merge_tier(8, 64, 10, explicit="bogus")

    def test_dispatch_counter(self, monkeypatch):
        from raft_tpu import obs
        from raft_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            merge_tier(8, 64, 10, explicit="ring")
            merge_tier(8, 64, 10, explicit="allgather")
        finally:
            obs.disable()
        c = reg.snapshot()["counters"]
        assert c["parallel.merge.dispatch{impl=ring_ppermute}"] == 1.0
        assert c["parallel.merge.dispatch{impl=allgather}"] == 1.0

    def test_ring_auto_shape_gate(self):
        # tiny batches: mc pads to 8 rows, the ring would ship MORE
        # bytes over n_dev−1 serial hops — auto must keep allgather
        assert not ring_auto_wanted(4, 10, 8)
        assert not ring_auto_wanted(8, 10, 8)
        # bandwidth-bound batches: the ring's counted bytes are ≤ half
        # the allgather's (the scaling CI's ≥2× bar)
        assert ring_auto_wanted(256, 10, 8)
        assert ring_auto_wanted(64, 10, 2)

    @pytest.mark.slow  # full sharded trace for a validation path; CI lanes run it (tier-1 budget)
    def test_sharded_search_validates_queries(self, mesh, rng):
        # the sharded entry keeps the single-chip contract: bad query
        # dims fail the clear expects, not a shape error in shard_map
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.parallel import build_ivf_flat, search_ivf_flat

        x = jnp.asarray(rng.random((512, 16), dtype=np.float32))
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=2)
        sharded = build_ivf_flat(params, x, mesh)
        with pytest.raises(Exception, match=r"queries must be \[m, 16\]"):
            search_ivf_flat(ivf_flat.SearchParams(n_probes=4), sharded,
                            jnp.zeros((4, 7)), 3, mesh)

    def test_out_spec_and_rows(self):
        assert merge_out_spec("allgather", "shard") == P()
        assert merge_out_spec("ring", "shard") == P("shard", None)
        assert merged_rows("allgather", 27, 8) == 27
        assert merged_rows("ring", 27, 8) == pk.ring_chunk_rows(27, 8) * 8


class TestRingBytes:
    """Exact per-hop accounting: n_dev−1 ops, one surviving-block
    payload per hop, for BOTH ring impls — and the allgather tier's
    materialized-table model beside them."""

    @pytest.fixture()
    def reg(self):
        from raft_tpu import obs
        from raft_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        yield reg
        obs.disable()

    @pytest.mark.slow  # exact hop-byte model; CI lanes + the dryrun byte assertions cover it (tier-1 budget)
    def test_ring_hop_bytes_exact(self, mesh, reg, rng):
        m, k = 27, 10
        x = jnp.asarray(rng.random((803, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((m, 16), dtype=np.float32))
        sharded_knn(x, q, k, mesh, merge="ring")
        c = reg.snapshot()["counters"]
        mc = pk.ring_chunk_rows(m, N_DEV)
        hop = mc * k * (4 + 4)  # f32 vals + i32 ids per surviving block
        assert c["comms.ops{axis=shard,op=ring_topk}"] == N_DEV - 1, c
        assert c["comms.bytes{axis=shard,op=ring_topk}"] == \
            (N_DEV - 1) * hop, c
        assert "comms.ops{axis=shard,op=allgather}" not in c, c

    def test_allgather_merge_bytes_exact(self, mesh, reg, rng):
        m, k = 27, 10
        x = jnp.asarray(rng.random((803, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((m, 16), dtype=np.float32))
        sharded_knn(x, q, k, mesh, merge="allgather")
        c = reg.snapshot()["counters"]
        # two gathers (vals + ids), each materializing size × [m, k]
        assert c["comms.bytes{axis=shard,op=allgather}"] == \
            N_DEV * m * k * 4 * 2, c

    @pytest.mark.slow  # ratio re-proved by the dryrun byte model + exact-byte twins above; CI lanes run it (tier-1 budget)
    def test_ring_beats_allgather_2x(self, mesh, reg, rng):
        # the ISSUE 8 acceptance ratio at n_dev=8, in the counters
        m, k = 256, 10
        x = jnp.asarray(rng.random((2048, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((m, 16), dtype=np.float32))
        sharded_knn(x, q, k, mesh, merge="allgather")
        sharded_knn(x, q, k, mesh, merge="ring")
        c = reg.snapshot()["counters"]
        ag = c["comms.bytes{axis=shard,op=allgather}"]
        ring = c["comms.bytes{axis=shard,op=ring_topk}"]
        assert 2 * ring <= ag, (ring, ag)

    @pytest.mark.slow  # two full impl traces; CI lanes run it
    def test_kernel_impl_counts_like_fallback(self, mesh, reg, rng):
        # count_ring_topk (kernel path) == per-hop ring_topk_hop counts
        m, k = 40, 8
        vals, ids = make_tables(rng, m, k, True)

        def run(impl):
            def body(v, i):
                return merge_topk(v[0], i[0], "shard", m, k, N_DEV, True,
                                  tier="ring", impl=impl, interpret=True)

            fn = shard_map(
                body, mesh=mesh,
                in_specs=(P("shard", None, None), P("shard", None, None)),
                out_specs=(P("shard", None), P("shard", None)),
                check_vma=False)
            jax.block_until_ready(fn(jnp.asarray(vals), jnp.asarray(ids)))

        run("ring_kernel")
        kc = dict(reg.snapshot()["counters"])
        reg.reset()
        run("ring_ppermute")
        fc = reg.snapshot()["counters"]
        for key in ("comms.ops{axis=shard,op=ring_topk}",
                    "comms.bytes{axis=shard,op=ring_topk}"):
            assert kc[key] == fc[key], (key, kc, fc)


class TestRingSchedule:
    """The ring merge under the collective-schedule checker: one
    device-uniform schedule, with the facade recorder attributing
    exactly n_dev−1 ring_topk hops."""

    def test_ring_knn_schedule_uniform(self, mesh, rng):
        x = jnp.asarray(rng.random((256, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((16, 16), dtype=np.float32))
        with sanitize.record_comms_schedule() as rec:
            sched = sanitize.assert_uniform_collective_schedule(
                lambda: sharded_knn(x, q, 4, mesh, merge="ring"))
        hops = [e for e in rec if e[0] == "ring_topk"]
        assert len(hops) == N_DEV - 1, rec
        mc = pk.ring_chunk_rows(16, N_DEV)
        assert all(a == "shard" and b == mc * 4 * 8
                   for _, a, b in hops), rec
        verbs = [e[0] for e in _flat(sched)]
        # vals + ids move per hop: 2(n_dev−1) ppermutes, no all_gather
        assert verbs.count("ppermute") == 2 * (N_DEV - 1), verbs
        assert verbs.count("all_gather") == 0, verbs


def _flat(sched):
    for e in sched:
        if len(e) == 2:  # ("while"|"scan", inner)
            yield from _flat(e[1])
        else:
            yield e


@pytest.fixture(scope="module")
def pq_sharded(mesh):
    """A small sharded IVF-PQ index + its build data (module-scoped:
    the distributed build is the expensive part)."""
    from raft_tpu.neighbors import ivf_pq as _pq
    from raft_tpu.parallel import build_ivf_pq

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.random((1024, 32), dtype=np.float32))
    params = _pq.IndexParams(n_lists=16, pq_dim=8, pq_bits=4,
                             kmeans_n_iters=3)
    return build_ivf_pq(params, x, mesh), x


class TestRingFusedScan:
    """ISSUE 11 tentpole, second half: the fused scan-in-ring tier —
    per-shard LUT scan folded into the ring exchange, exact parity with
    the unfused sharded search, unchanged byte model, every decline
    rung preserved."""

    def _search(self, idx, q, k, mesh, merge="ring", n_probes=4,
                lut_dtype="float32", scan_select="pallas",
                filter_bitset=None):
        from raft_tpu.neighbors import ivf_pq as _pq
        from raft_tpu.parallel import search_ivf_pq

        # scan_select="pallas": the fused tier carries the LUT-bin
        # tier's selection semantics, so it only serves searches the
        # single-chip dispatch would route there (default "exact"
        # declines with reason=scan_select)
        sp = _pq.SearchParams(n_probes=n_probes, lut_dtype=lut_dtype,
                              scan_select=scan_select)
        return search_ivf_pq(sp, idx, q, k, mesh, merge=merge,
                             filter_bitset=filter_bitset)

    @pytest.mark.slow  # parity twin re-asserted by the dryrun fused-identity leg; CI runs it (tier-1 budget)
    def test_fused_matches_unfused(self, mesh, rng, pq_sharded,
                                   monkeypatch):
        idx, _ = pq_sharded
        q = jnp.asarray(rng.random((77, 32), dtype=np.float32))  # ragged
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "off")
        va, ia = self._search(idx, q, 8, mesh, merge="allgather")
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "on")
        vf, iff = self._search(idx, q, 8, mesh, merge="ring")
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(iff))
        np.testing.assert_allclose(np.asarray(va), np.asarray(vf),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.slow  # two sharded searches + fused trace; CI runs it
    def test_fused_filtered_matches_unfused(self, mesh, rng, pq_sharded,
                                            monkeypatch):
        """ISSUE 12: filtered pod-scale search rides the ring kernel —
        the per-shard bitset slice streams beside the codes, results
        identical to the unfused filtered allgather path, no filtered
        id ever crossing the ring, and the fused dispatch counted with
        filtered=1 while the retired filter_bitset reason stays zero."""
        from raft_tpu import obs
        from raft_tpu.core import bitset
        from raft_tpu.obs.metrics import MetricsRegistry

        idx, x = pq_sharded
        n = x.shape[0]
        keep = np.asarray(rng.random(n) < 0.4)
        bits = bitset.from_mask(jnp.asarray(keep))
        q = jnp.asarray(rng.random((64, 32), dtype=np.float32))
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "off")
        va, ia = self._search(idx, q, 8, mesh, merge="allgather",
                              filter_bitset=bits)
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "on")
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            vf, iff = self._search(idx, q, 8, mesh, merge="ring",
                                   filter_bitset=bits)
            jax.block_until_ready((vf, iff))
        finally:
            obs.disable()
        ia, iff = np.asarray(ia), np.asarray(iff)
        assert keep[ia[ia >= 0]].all() and keep[iff[iff >= 0]].all()
        np.testing.assert_array_equal(ia, iff)
        np.testing.assert_allclose(np.asarray(va), np.asarray(vf),
                                   rtol=1e-4, atol=1e-4)
        c = reg.snapshot()["counters"]
        assert c.get(
            "ivf_pq.scan.dispatch{filtered=1,impl=ring_lut_fused}",
            0) == 1.0, c
        assert c.get("ivf_pq.scan.fallback{reason=filter_bitset}",
                     0) == 0, c

    @pytest.mark.slow  # sole tier-1 user of the pq_sharded build; the fused CI legs exercise admission (tier-1 budget)
    def test_fused_filtered_admission(self, pq_sharded, monkeypatch):
        """_ring_fused_wanted(filtered=True) admits the workhorse shape
        (the filter slots fit the VMEM model and the byte rows pass
        filtered_scan_mem_ok) — filtered searches stay on the tier."""
        from raft_tpu.distance.types import DistanceType
        from raft_tpu.parallel.ivf import _ring_fused_wanted

        idx, _ = pq_sharded
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "on")
        args = dict(m=64, k=8, n_probes=4, n_dev=N_DEV, whole_mesh=True,
                    merge="ring", mt=DistanceType.L2Expanded,
                    lut_dtype="float32", scan_select="pallas")
        take, reason = _ring_fused_wanted(idx, filtered=True, **args)
        assert (take, reason) == (True, "")

    @pytest.mark.slow  # own sharded build + fused kernel trace
    def test_fused_inner_product(self, mesh, rng, monkeypatch):
        from raft_tpu.neighbors import ivf_pq as _pq
        from raft_tpu.parallel import build_ivf_pq

        x = jnp.asarray(rng.random((768, 32), dtype=np.float32))
        q = jnp.asarray(rng.random((40, 32), dtype=np.float32))
        idx = build_ivf_pq(
            _pq.IndexParams(n_lists=8, pq_dim=8, pq_bits=4,
                            kmeans_n_iters=2, metric="inner_product"),
            x, mesh)
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "off")
        va, ia = self._search(idx, q, 5, mesh, merge="allgather")
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "on")
        vf, iff = self._search(idx, q, 5, mesh, merge="ring")
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(iff))
        np.testing.assert_allclose(np.asarray(va), np.asarray(vf),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.slow  # two more full sharded traces; CI lanes run it
    def test_fused_dispatch_counters_and_bytes(self, mesh, rng,
                                               pq_sharded, monkeypatch):
        from raft_tpu import obs
        from raft_tpu.obs.metrics import MetricsRegistry

        idx, _ = pq_sharded
        q = jnp.asarray(rng.random((64, 32), dtype=np.float32))

        def run(fused):
            monkeypatch.setenv("RAFT_TPU_RING_FUSED", fused)
            reg = MetricsRegistry()
            obs.enable(registry=reg, hbm=False)
            try:
                jax.block_until_ready(
                    self._search(idx, q, 8, mesh, merge="ring"))
            finally:
                obs.disable()
            return reg.snapshot()["counters"]

        cf = run("on")
        assert cf["parallel.merge.dispatch{impl=ring_fused_scan}"] == 1.0
        assert cf["ivf_pq.scan.dispatch{impl=ring_lut_fused}"] == 1.0
        cu = run("off")
        # the fusion moves compute, not bytes: identical ring hop model
        key_ops = "comms.ops{axis=shard,op=ring_topk}"
        key_b = "comms.bytes{axis=shard,op=ring_topk}"
        assert cf[key_ops] == cu[key_ops] == N_DEV - 1
        assert cf[key_b] == cu[key_b] > 0

    @pytest.mark.slow  # one more full fused-kernel trace
    def test_fused_schedule_uniform(self, mesh, rng, pq_sharded,
                                    monkeypatch):
        idx, _ = pq_sharded
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "on")
        q = jnp.asarray(rng.random((32, 32), dtype=np.float32))
        with sanitize.record_comms_schedule() as rec:
            sanitize.assert_uniform_collective_schedule(
                lambda: self._search(idx, q, 4, mesh, merge="ring"))
        hops = [e for e in rec if e[0] == "ring_topk"]
        assert len(hops) == N_DEV - 1, rec

    @pytest.mark.slow  # x64 retrace of the whole sharded search
    def test_int64_ids_decline_fused(self, mesh, rng, pq_sharded,
                                     monkeypatch):
        """The id-width admission is preserved through the fused tier:
        an int64 id table declines the fused kernel (int32-only) AND
        the plain ring kernel, landing on the identical-schedule
        ppermute fallback — counted, never truncated."""
        from raft_tpu import obs
        from raft_tpu.obs import sanitize as _san
        from raft_tpu.obs.metrics import MetricsRegistry

        idx, _ = pq_sharded
        q = jnp.asarray(rng.random((64, 32), dtype=np.float32))
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "on")
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            # trace-only under scoped x64, like the plain-ring id-width
            # test: the declines are trace-time dtype checks
            with _san.scoped_x64(True):
                idx64 = idx.replace(
                    packed_ids=idx.packed_ids.astype(jnp.int64))
                closed = jax.make_jaxpr(
                    lambda qq: self._search(idx64, qq, 8, mesh,
                                            merge="ring"))(q)
        finally:
            obs.disable()
        c = reg.snapshot()["counters"]
        assert c.get("parallel.merge.fallback{reason=id_width}", 0) >= 1.0
        assert "ivf_pq.scan.dispatch{impl=ring_lut_fused}" not in c
        # merged ids keep their 64-bit width end to end
        assert "int64" in str(closed.jaxpr.outvars[1].aval)

    @pytest.mark.slow  # own sharded build
    def test_cosine_declines_fused(self, mesh, rng, monkeypatch):
        from raft_tpu import obs
        from raft_tpu.neighbors import ivf_pq as _pq
        from raft_tpu.obs.metrics import MetricsRegistry
        from raft_tpu.parallel import build_ivf_pq

        x = jnp.asarray(rng.random((512, 32), dtype=np.float32))
        q = jnp.asarray(rng.random((40, 32), dtype=np.float32))
        idx = build_ivf_pq(
            _pq.IndexParams(n_lists=8, pq_dim=8, pq_bits=4,
                            kmeans_n_iters=2, metric="cosine"),
            x, mesh)
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "on")
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            jax.block_until_ready(
                self._search(idx, q, 5, mesh, merge="ring"))
        finally:
            obs.disable()
        c = reg.snapshot()["counters"]
        assert c.get("parallel.merge.fallback{reason=metric}", 0) == 1.0
        assert "ivf_pq.scan.dispatch{impl=ring_lut_fused}" not in c

    def test_exact_scan_select_declines(self, pq_sharded, monkeypatch):
        """The default scan_select="exact" must never be silently
        swapped for the bin tier's recall-targeted selection — even
        under env force the fused tier declines (reason=scan_select)
        unless the single-chip dispatch would have picked the LUT
        tier."""
        from raft_tpu.distance.types import DistanceType
        from raft_tpu.parallel.ivf import _ring_fused_wanted

        idx, _ = pq_sharded
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "on")
        args = dict(m=64, k=8, n_probes=4, n_dev=N_DEV, whole_mesh=True,
                    merge="ring", mt=DistanceType.L2Expanded,
                    lut_dtype="float32")
        take, reason = _ring_fused_wanted(idx, scan_select="exact",
                                          **args)
        assert (take, reason) == (False, "scan_select")
        take, reason = _ring_fused_wanted(idx, scan_select="pallas",
                                          **args)
        assert (take, reason) == (True, "")
        # "approx" only at the oversampled auto-upgrade shape
        take, reason = _ring_fused_wanted(idx, scan_select="approx",
                                          **args)
        assert (take, reason) == (False, "scan_select")

    @pytest.mark.slow  # one more sharded trace; CI lanes run it
    def test_env_off_keeps_plain_path(self, mesh, rng, pq_sharded,
                                      monkeypatch):
        from raft_tpu import obs
        from raft_tpu.obs.metrics import MetricsRegistry

        idx, _ = pq_sharded
        q = jnp.asarray(rng.random((64, 32), dtype=np.float32))
        monkeypatch.setenv("RAFT_TPU_RING_FUSED", "off")
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            jax.block_until_ready(
                self._search(idx, q, 8, mesh, merge="ring"))
        finally:
            obs.disable()
        c = reg.snapshot()["counters"]
        assert "parallel.merge.dispatch{impl=ring_fused_scan}" not in c
        assert c["parallel.merge.dispatch{impl=ring_ppermute}"] == 1.0
