"""Distributed tests on the virtual 8-device CPU mesh (reference test model:
raft_dask/test/test_comms.py — collective self-checks per worker; here the
collectives run for real across 8 XLA host devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P
from scipy.spatial.distance import cdist

from raft_tpu.parallel import Comms, Op, make_mesh, replicated_knn, sharded_knn


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(axis_names=("shard",))


N_DEV = 8


class TestComms:
    """Collective correctness (reference: perform_test_comms_* trampolines,
    raft_dask/common/comms_utils.pyx:78+)."""

    def _run(self, fn, x, mesh, in_spec=P("shard"), out_spec=P("shard")):
        return shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec, check_vma=False)(x)

    def test_allreduce_sum(self, mesh):
        comms = Comms("shard")
        x = jnp.arange(N_DEV, dtype=jnp.float32)
        out = self._run(lambda v: comms.allreduce(v, Op.SUM), x, mesh)
        np.testing.assert_allclose(np.asarray(out), np.full(N_DEV, x.sum()))

    def test_allreduce_max_min(self, mesh):
        comms = Comms("shard")
        x = jnp.arange(N_DEV, dtype=jnp.float32)
        out = self._run(lambda v: comms.allreduce(v, Op.MAX), x, mesh)
        np.testing.assert_allclose(np.asarray(out), np.full(N_DEV, N_DEV - 1))
        out = self._run(lambda v: comms.allreduce(v, Op.MIN), x, mesh)
        np.testing.assert_allclose(np.asarray(out), np.zeros(N_DEV))

    def test_bcast(self, mesh):
        comms = Comms("shard")
        x = jnp.arange(N_DEV, dtype=jnp.float32)
        out = self._run(lambda v: comms.bcast(v, root=3), x, mesh)
        np.testing.assert_allclose(np.asarray(out), np.full(N_DEV, 3.0))

    def test_allgather(self, mesh):
        comms = Comms("shard")
        x = jnp.arange(N_DEV, dtype=jnp.float32)
        out = shard_map(lambda v: comms.allgather(v), mesh=mesh,
                        in_specs=(P("shard"),), out_specs=P("shard", None),
                        check_vma=False)(x)
        assert out.shape == (N_DEV * N_DEV, 1) or out.shape == (N_DEV, N_DEV)

    def test_reducescatter(self, mesh):
        comms = Comms("shard")
        x = jnp.ones((N_DEV * N_DEV,), jnp.float32)
        out = self._run(lambda v: comms.reducescatter(v, Op.SUM), x, mesh)
        np.testing.assert_allclose(np.asarray(out), np.full(N_DEV, N_DEV))

    def test_ring_permute(self, mesh):
        comms = Comms("shard")
        x = jnp.arange(N_DEV, dtype=jnp.float32)
        out = self._run(lambda v: comms.send_recv_ring(v, shift=1), x, mesh)
        expected = np.roll(np.arange(N_DEV, dtype=np.float32), 1)
        np.testing.assert_allclose(np.asarray(out), expected)

    def test_allgatherv(self, mesh):
        # ragged shards: rank r holds (r % 3) + 1 valid rows in a cap-4
        # padded buffer; compacted output packs valid rows front, in
        # rank order (reference: comms_t::allgatherv, core/comms.hpp:423)
        comms = Comms("shard")
        cap = 4
        counts_h = np.array([(r % 3) + 1 for r in range(N_DEV)], np.int32)
        x = np.full((N_DEV * cap, 2), -1.0, np.float32)
        for r in range(N_DEV):
            for i in range(counts_h[r]):
                x[r * cap + i] = r * 10 + i
        out, cnts = shard_map(
            lambda v, c: comms.allgatherv(v, c[0]),
            mesh=mesh, in_specs=(P("shard"), P("shard")),
            out_specs=(P(None), P(None)), check_vma=False)(
                jnp.asarray(x), jnp.asarray(counts_h))
        total = int(counts_h.sum())
        expect = np.concatenate(
            [x[r * cap: r * cap + counts_h[r]] for r in range(N_DEV)])
        np.testing.assert_allclose(np.asarray(out)[:total], expect)
        np.testing.assert_array_equal(np.asarray(cnts), counts_h)
        # gatherv aliases the same packing
        out2, _ = shard_map(
            lambda v, c: comms.gatherv(v, c[0], root=2),
            mesh=mesh, in_specs=(P("shard"), P("shard")),
            out_specs=(P(None), P(None)), check_vma=False)(
                jnp.asarray(x), jnp.asarray(counts_h))
        np.testing.assert_allclose(np.asarray(out2)[:total], expect)

    def test_rank_size(self, mesh):
        comms = Comms("shard")
        x = jnp.zeros((N_DEV,), jnp.int32)
        out = self._run(lambda v: v + comms.get_rank(), x, mesh)
        np.testing.assert_array_equal(np.asarray(out), np.arange(N_DEV))


class TestCommsTelemetry:
    """ISSUE 5: every collective counts ops + per-rank payload bytes
    into ``comms.ops{op=...,axis=...}`` / ``comms.bytes{...}`` from
    static shape/dtype (once per trace, zero host syncs) — here run for
    real on the 8-device CPU mesh."""

    @pytest.fixture()
    def reg(self):
        from raft_tpu import obs
        from raft_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        yield reg
        obs.disable()
        obs.get_registry().reset()

    def _counters(self, reg):
        return reg.snapshot()["counters"]

    def test_allreduce_counts_ops_and_bytes(self, mesh, reg):
        comms = Comms("shard")
        x = jnp.arange(N_DEV, dtype=jnp.float32)  # [1] f32 per shard
        out = shard_map(lambda v: comms.allreduce(v, Op.SUM), mesh=mesh,
                        in_specs=(P("shard"),), out_specs=P("shard"),
                        check_vma=False)(x)
        np.testing.assert_allclose(np.asarray(out), np.full(N_DEV, x.sum()))
        c = self._counters(reg)
        assert c["comms.ops{axis=shard,op=allreduce}"] == 1.0
        assert c["comms.bytes{axis=shard,op=allreduce}"] == 4.0  # 1·f32

    def test_byte_totals_per_collective(self, mesh, reg):
        comms = Comms("shard")
        # [16, 2] f32 per shard = 128 payload bytes; fixed-size-result
        # verbs count the payload, the gather family counts the
        # size×payload table it materializes over the interconnect
        x = jnp.ones((N_DEV * 16, 2), jnp.float32)

        def body(v):
            g = comms.allgather(v)                       # 8 × 128 B
            r = comms.reducescatter(
                comms.alltoall(v) + v, Op.SUM)           # 128 B each
            s = comms.send_recv_ring(v)                  # 128 B
            return (jnp.sum(g) + jnp.sum(r) + jnp.sum(s))[None]

        shard_map(body, mesh=mesh, in_specs=(P("shard"),),
                  out_specs=P("shard"), check_vma=False)(x)
        c = self._counters(reg)
        for verb, want in (("allgather", N_DEV * 128.0),
                           ("alltoall", 128.0), ("reducescatter", 128.0),
                           ("send_recv_ring", 128.0)):
            assert c[f"comms.ops{{axis=shard,op={verb}}}"] == 1.0, (verb, c)
            assert c[f"comms.bytes{{axis=shard,op={verb}}}"] == want, \
                (verb, c)

    def test_allgatherv_counts_payload_plus_count(self, mesh, reg):
        comms = Comms("shard")
        cap = 4
        x = jnp.ones((N_DEV * cap, 2), jnp.float32)
        counts = jnp.ones((N_DEV,), jnp.int32)
        shard_map(lambda v, n: comms.allgatherv(v, n[0]), mesh=mesh,
                  in_specs=(P("shard"), P("shard")),
                  out_specs=(P(None), P(None)), check_vma=False)(x, counts)
        c = self._counters(reg)
        assert c["comms.ops{axis=shard,op=allgatherv}"] == 1.0
        # gather family counts the materialized table:
        # 8 × ([4, 2] f32 rows + one i32 count) = 8 × 36
        assert c["comms.bytes{axis=shard,op=allgatherv}"] == N_DEV * 36.0

    def test_counted_once_per_trace_not_per_execution(self, mesh, reg):
        comms = Comms("shard")
        x = jnp.arange(N_DEV, dtype=jnp.float32)
        fn = shard_map(lambda v: comms.allreduce(v), mesh=mesh,
                       in_specs=(P("shard"),), out_specs=P("shard"),
                       check_vma=False)
        jfn = jax.jit(fn)
        for _ in range(3):  # 2nd/3rd call hit the jit cache: no retrace
            jax.block_until_ready(jfn(x))
        c = self._counters(reg)
        assert c["comms.ops{axis=shard,op=allreduce}"] == 1.0, c

    def test_two_axis_mesh_attributes_per_axis(self, reg):
        # DCN×ICI-shaped mesh: sub-communicator traffic must label its
        # own axis, and a WORLD (tuple-axis) collective must decompose
        # into one counted stage per constituent axis instead of the
        # old lumped dcn+ici label (the per-axis attribution the
        # MULTICHIP record and the per-axis roofline need)
        mesh2 = make_mesh(shape=(2, N_DEV // 2), axis_names=("dcn", "ici"))
        world = Comms(("dcn", "ici"))
        ici, dcn = world.comm_split("ici"), world.comm_split("dcn")

        def hier(v):
            return dcn.allreduce(ici.allreduce(v)) + world.allreduce(v)

        out = shard_map(hier, mesh=mesh2, in_specs=(P(("dcn", "ici")),),
                        out_specs=P(("dcn", "ici")), check_vma=False)(
            jnp.arange(N_DEV, dtype=jnp.float32))
        expect = 2 * N_DEV * (N_DEV - 1) // 2
        np.testing.assert_allclose(np.asarray(out),
                                   np.full(N_DEV, float(expect)))
        c = self._counters(reg)
        # one explicit sub-axis allreduce each + one per-axis stage of
        # the world allreduce = 2 ops / 2×4 B per axis; no lumped key
        assert c["comms.ops{axis=ici,op=allreduce}"] == 2.0
        assert c["comms.ops{axis=dcn,op=allreduce}"] == 2.0
        for axis in ("ici", "dcn"):
            assert c[f"comms.bytes{{axis={axis},op=allreduce}}"] == 8.0
        assert not any("dcn+ici" in key for key in c), c

    def test_world_gather_family_charges_cumulative_stages(self, reg):
        # gather-family payload grows as it climbs the hierarchy: the
        # inner stage materializes size(inner)×payload, the outer stage
        # ships THAT times size(outer) — the byte model that keeps a
        # world allgather honest about what actually crosses DCN
        mesh2 = make_mesh(shape=(2, N_DEV // 2), axis_names=("dcn", "ici"))
        world = Comms(("dcn", "ici"))
        shard_map(lambda v: jnp.sum(world.allgather(v))[None],
                  mesh=mesh2, in_specs=(P(("dcn", "ici")),),
                  out_specs=P(("dcn", "ici")), check_vma=False)(
            jnp.arange(N_DEV, dtype=jnp.float32))
        c = self._counters(reg)
        # payload 4 B: ici stage 4×4 = 16, dcn stage 16×2 = 32
        assert c["comms.bytes{axis=ici,op=allgather}"] == 16.0
        assert c["comms.bytes{axis=dcn,op=allgather}"] == 32.0

    def test_sharded_knn_and_distributed_kmeans_count(self, mesh, reg,
                                                      rng):
        # the dryrun legs must leave nonzero comm counters (the
        # MULTICHIP acceptance): sharded kNN merges via allgather,
        # distributed kmeans merges via allreduce
        from raft_tpu.cluster import KMeansParams
        from raft_tpu.cluster import distributed as dkm

        x = jnp.asarray(rng.random((64, 8), dtype=np.float32))
        q = jnp.asarray(rng.random((4, 8), dtype=np.float32))
        sharded_knn(x, q, 3, mesh)
        dkm.fit(KMeansParams(n_clusters=4, max_iter=2, seed=0), x, mesh)
        c = self._counters(reg)
        assert c.get("comms.ops{axis=shard,op=allgather}", 0) >= 2.0, c
        assert c.get("comms.ops{axis=shard,op=allreduce}", 0) >= 3.0, c
        assert c.get("comms.bytes{axis=shard,op=allreduce}", 0) > 0, c


class TestCollectiveSchedule:
    """Distributed entry points gated by the collective-schedule
    checker (raft_tpu.obs.sanitize): the schedule each traced program
    commits every device to must be conditional-free-or-uniform, and
    must contain the collectives the telemetry attributes."""

    def _flat(self, sched):
        for e in sched:
            if len(e) == 2:  # ("while"|"scan", inner)
                yield from self._flat(e[1])
            else:
                yield e

    def test_sharded_knn_schedule_uniform(self, mesh, rng):
        from raft_tpu.obs import sanitize

        x = jnp.asarray(rng.random((64, 8), dtype=np.float32))
        q = jnp.asarray(rng.random((4, 8), dtype=np.float32))
        sched = sanitize.assert_uniform_collective_schedule(
            lambda: sharded_knn(x, q, 3, mesh))
        verbs = [e[0] for e in self._flat(sched)]
        assert verbs.count("all_gather") == 2, verbs  # vals + ids merge

    def test_distributed_kmeans_schedule_uniform(self, mesh, rng):
        from raft_tpu.cluster import KMeansParams
        from raft_tpu.cluster import distributed as dkm
        from raft_tpu.obs import sanitize

        x = jnp.asarray(rng.random((64, 8), dtype=np.float32))
        sched = sanitize.assert_uniform_collective_schedule(
            lambda: dkm.fit(KMeansParams(n_clusters=4, max_iter=2,
                                         seed=0), x, mesh))
        verbs = [e[0] for e in self._flat(sched)]
        # sums + counts + inertia psums per Lloyd iteration
        assert verbs.count("psum") >= 3, verbs


class TestShardedKnn:
    def test_sharded_matches_naive(self, mesh, rng):
        x = rng.random((803, 16), dtype=np.float32)  # non-divisible by 8
        q = rng.random((27, 16), dtype=np.float32)
        vals, ids = sharded_knn(jnp.asarray(x), jnp.asarray(q), 10, mesh)
        full = cdist(q, x, "sqeuclidean")
        ref_i = np.argsort(full, 1)[:, :10]
        hits = sum(len(set(g) & set(r)) for g, r in
                   zip(np.asarray(ids), ref_i))
        assert hits / ref_i.size >= 0.99
        np.testing.assert_allclose(
            np.sort(np.asarray(vals), 1),
            np.sort(np.take_along_axis(full, ref_i, 1), 1),
            rtol=1e-3, atol=1e-4)

    def test_replicated_matches_naive(self, mesh, rng):
        x = rng.random((200, 16), dtype=np.float32)
        q = rng.random((53, 16), dtype=np.float32)  # non-divisible by 8
        vals, ids = replicated_knn(jnp.asarray(x), jnp.asarray(q), 5, mesh)
        full = cdist(q, x, "sqeuclidean")
        ref_i = np.argsort(full, 1)[:, :5]
        hits = sum(len(set(g) & set(r)) for g, r in
                   zip(np.asarray(ids), ref_i))
        assert hits / ref_i.size >= 0.99

    def test_replicated_trainset_labels_match_naive(self, mesh, rng):
        """The sharded IVF-PQ build labels its mesh-replicated trainset
        inside shard_map; every device's copy gives the naive labels."""
        from jax.sharding import NamedSharding

        from raft_tpu.parallel import ivf as pivf

        rep = NamedSharding(mesh, P())
        t = jax.device_put(rng.random((300, 16), dtype=np.float32), rep)
        c = jax.device_put(rng.random((12, 16), dtype=np.float32), rep)
        labels = pivf._label_replicated(t, c, mesh)
        want = np.argmin(cdist(np.asarray(t), np.asarray(c),
                               "sqeuclidean"), 1)
        for shard in labels.addressable_shards:
            np.testing.assert_array_equal(np.asarray(shard.data), want)


class TestHierMerge:
    """The ISSUE-19 two-level merge: per-pod ring over ICI, one sparse
    survivor exchange over DCN — identity with the flat tiers, the
    O(k·pods) DCN byte model, and the dispatch/validation surface."""

    @pytest.fixture
    def reg(self):
        from raft_tpu import obs
        from raft_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        yield reg
        obs.disable()

    def _counters(self, reg):
        return reg.snapshot()["counters"]

    @pytest.mark.parametrize("dcn_size,ici_size", [(2, 4), (4, 2)])
    def test_hier_matches_flat_bit_identically(self, mesh, rng,
                                               dcn_size, ici_size):
        from raft_tpu.parallel import hier_mesh

        x = jnp.asarray(rng.random((1024, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((96, 16), dtype=np.float32))
        fv, fi = sharded_knn(x, q, 10, mesh, merge="allgather")
        mesh2 = hier_mesh(ici_size, dcn_size)
        hv, hi = sharded_knn(x, q, 10, mesh2, axis=("dcn", "ici"))
        assert np.array_equal(np.asarray(fi), np.asarray(hi))
        assert np.array_equal(np.asarray(fv), np.asarray(hv))

    def test_hier_dcn_bytes_match_survivor_model(self, rng, reg):
        from raft_tpu.parallel import hier_chunk_rows, hier_mesh

        m, k, n_inner, n_outer = 96, 10, 4, 2
        x = jnp.asarray(rng.random((1024, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((m, 16), dtype=np.float32))
        mesh2 = hier_mesh(n_inner, n_outer)
        sharded_knn(x, q, k, mesh2, axis=("dcn", "ici"))
        c = self._counters(reg)
        assert c["parallel.merge.dispatch{impl=hier}"] == 1.0
        mc = hier_chunk_rows(m, n_inner, n_outer)
        # k survivors per pod × owned sub-chunk rows, f32 vals + i32 ids
        model = n_outer * (mc // n_outer) * k * 8
        dcn = sum(v for key, v in c.items()
                  if key.startswith("comms.bytes{") and "axis=dcn" in key)
        ici = sum(v for key, v in c.items()
                  if key.startswith("comms.bytes{") and "axis=ici" in key)
        assert dcn == model, (dcn, model, c)
        assert ici > 0, c

    def test_hier_dcn_bytes_below_flat_ring_cross_pod(self, rng):
        from raft_tpu import obs
        from raft_tpu.obs.metrics import MetricsRegistry
        from raft_tpu.parallel import hier_mesh

        x = jnp.asarray(rng.random((1024, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((96, 16), dtype=np.float32))
        mesh2 = hier_mesh(4, 2)

        def dcn_bytes(**kw):
            reg = MetricsRegistry()
            obs.enable(registry=reg, hbm=False)
            try:
                sharded_knn(x, q, 10, mesh2, axis=("dcn", "ici"), **kw)
            finally:
                obs.disable()
            return sum(v for key, v in reg.snapshot()["counters"].items()
                       if key.startswith("comms.bytes{")
                       and "axis=dcn" in key)

        hier = dcn_bytes()
        # the topology-blind flat ring paces its whole stream cross-pod
        flat_ring = dcn_bytes(merge="ring")
        assert 0 < hier < flat_ring, (hier, flat_ring)

    def test_hier_env_off_falls_back_flat(self, rng, reg, monkeypatch):
        from raft_tpu.parallel import hier_mesh

        monkeypatch.setenv("RAFT_TPU_HIER_MERGE", "off")
        x = jnp.asarray(rng.random((256, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((16, 16), dtype=np.float32))
        mesh2 = hier_mesh(4, 2)
        fv, fi = sharded_knn(x, q, 5, mesh2, axis=("dcn", "ici"))
        c = self._counters(reg)
        assert "parallel.merge.dispatch{impl=hier}" not in c, c
        # explicit merge="hier" still overrides the env kill switch
        hv, hi = sharded_knn(x, q, 5, mesh2, axis=("dcn", "ici"),
                             merge="hier")
        assert np.array_equal(np.asarray(fi), np.asarray(hi))

    def test_merge_tier_dispatch_and_validation(self, reg):
        from raft_tpu.core.errors import LogicError
        from raft_tpu.parallel import merge_tier

        assert merge_tier(8, 256, 10,
                          hier_axes=("dcn", "ici", 2, 4)) == ("hier",
                                                              "hier")
        with pytest.raises(LogicError, match="hier"):
            merge_tier(8, 256, 10, explicit="hier")  # 1-D exchange
        c = self._counters(reg)
        assert c["parallel.merge.dispatch{impl=hier}"] == 1.0

    def test_merge_tier_env_on_without_axes_counts_fallback(
            self, reg, monkeypatch):
        from raft_tpu.parallel import merge_tier

        monkeypatch.setenv("RAFT_TPU_HIER_MERGE", "on")
        tier, _ = merge_tier(8, 256, 10)
        assert tier != "hier"
        c = self._counters(reg)
        assert c["parallel.merge.fallback{reason=no_hier_axes}"] == 1.0

    def test_hier_mesh_validates_axis_naming(self, mesh):
        from raft_tpu.core.errors import LogicError
        from raft_tpu.parallel import hier_mesh, submesh

        with pytest.raises(ValueError, match="slow axis must be outermost"):
            hier_mesh(4, 2, axis_names=("fast", "ici"))
        with pytest.raises(ValueError, match="DCN-labeled"):
            hier_mesh(4, 2, axis_names=("dcn", "pod2"))
        with pytest.raises(ValueError, match="slow axis must be outermost"):
            submesh(mesh, 8, ("ici", "dcn"), shape=(2, 4))
        with pytest.raises(ValueError, match="explicit shape"):
            submesh(mesh, 8, ("dcn", "ici"))
        m2 = submesh(mesh, 8, ("dcn", "ici"), shape=(2, 4))
        assert dict(zip(m2.axis_names, m2.devices.shape)) == \
            {"dcn": 2, "ici": 4}

    def test_non_dcn_outer_tuple_stays_flat(self, rng, reg):
        from raft_tpu.parallel import make_mesh as mk

        # a 2-D exchange whose outer axis is NOT DCN-labeled merges
        # flat (no hier auto-escalation, no hier dispatch counter)
        mesh2 = mk(shape=(2, 4), axis_names=("rows", "cols"))
        x = jnp.asarray(rng.random((256, 16), dtype=np.float32))
        q = jnp.asarray(rng.random((16, 16), dtype=np.float32))
        sharded_knn(x, q, 5, mesh2, axis=("rows", "cols"))
        c = self._counters(reg)
        assert "parallel.merge.dispatch{impl=hier}" not in c, c
