"""raft_tpu.serve — resilient online serving (ISSUE 14 tentpole).

The chaos-lane contract under test: injected OOM mid-batch walks the
degrade ladder and returns exact results; a full queue rejects with a
typed shed error (never a hang); registry eviction under synthetic HBM
pressure picks the LRU cold tenant; an injected SIGTERM leaves a
parseable flight dump carrying the serve counters; and steady-state
serving triggers ZERO recompiles under ``recompile_budget(0)``.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.obs import sanitize
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.neighbors import ivf_flat, ivf_pq
from raft_tpu.robust import degrade, faults, retry
from raft_tpu import serve
from raft_tpu.serve import loadgen

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, DIM = 3000, 32


@pytest.fixture(autouse=True)
def _clean():
    faults.clear_plan()
    degrade.clear_recent()
    yield
    faults.clear_plan()
    obs.disable()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    return rng.random((N, DIM), dtype=np.float32)


@pytest.fixture(scope="module")
def pq_index(data):
    return ivf_pq.build(jnp.asarray(data), ivf_pq.IndexParams(
        n_lists=16, pq_dim=16, seed=0, cache_reconstruction="never"))


@pytest.fixture(scope="module")
def flat_index(data):
    return ivf_flat.build(jnp.asarray(data),
                          ivf_flat.IndexParams(n_lists=16))


PQ_PARAMS = ivf_pq.SearchParams(n_probes=8, scan_mode="per_query")


def _registry_with(pq_index, flat_index=None):
    reg = serve.IndexRegistry(budget_bytes=1 << 30)
    reg.admit("pq", pq_index, params=PQ_PARAMS, default_k=10)
    if flat_index is not None:
        reg.admit("flat", flat_index,
                  params=ivf_flat.SearchParams(n_probes=8), default_k=10)
    return reg


def _counters(reg):
    return reg.snapshot()["counters"]


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------

class TestBuckets:
    def test_bucket_sizes_are_powers_of_two(self):
        assert serve.bucket_sizes(8) == (1, 2, 4, 8)
        assert serve.bucket_sizes(1) == (1,)
        assert serve.bucket_sizes(5) == (1, 2, 4, 8)  # rounded up

    def test_bucket_for_picks_smallest_fit(self):
        b = serve.bucket_sizes(16)
        assert serve.bucket_for(1, b) == 1
        assert serve.bucket_for(3, b) == 4
        assert serve.bucket_for(16, b) == 16

    def test_invalid_max_batch(self):
        with pytest.raises(ValueError):
            serve.bucket_sizes(0)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_admit_get_touch_and_peek(self):
        reg = serve.IndexRegistry(budget_bytes=1000)
        reg.admit("a", object(), size_bytes=100)
        t = reg.get("a")
        assert t.state == "warming"
        before = t.last_used
        time.sleep(0.005)
        # peek validates without heating the LRU clock; get touches it
        assert reg.peek("a").last_used == before
        assert reg.get("a").last_used > before
        with pytest.raises(serve.TenantUnknown):
            reg.peek("nope")

    def test_index_device_bytes_counts_leaves(self, pq_index):
        nbytes = serve.index_device_bytes(pq_index)
        # at minimum the packed codes + ids + norms are in there
        assert nbytes > int(pq_index.packed_codes.nbytes)

    def test_unknown_and_terminal_tenants_are_typed(self):
        reg = serve.IndexRegistry(budget_bytes=1000)
        with pytest.raises(serve.TenantUnknown):
            reg.get("nope")
        reg.admit("a", object(), size_bytes=10)
        reg.evict("a")
        with pytest.raises(serve.TenantUnknown) as ei:
            reg.get("a")
        assert ei.value.state == "evicted"

    def test_eviction_under_pressure_picks_lru_cold_tenant(self):
        """The ISSUE's named chaos case: synthetic HBM pressure (tight
        byte budget) must evict the LEAST-recently-used tenant, not the
        hottest one."""
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        reg = serve.IndexRegistry(budget_bytes=400, headroom_frac=0.0)
        for name in ("t1", "t2", "t3"):
            reg.admit(name, object(), size_bytes=100)
        time.sleep(0.002)
        reg.get("t1")  # t1 and t3 are hot, t2 is the cold one
        reg.get("t3")
        reg.admit("t4", object(), size_bytes=150)  # needs one eviction
        states = {t.name: t.state for t in reg.tenants()}
        assert states == {"t1": "warming", "t2": "evicted",
                          "t3": "warming", "t4": "warming"}
        c = _counters(mreg)
        assert c["serve.registry.evict{reason=pressure,tenant=t2}"] == 1.0
        assert c["serve.registry.admit{tenant=t4}"] == 1.0
        assert reg.resident_bytes() == 350

    def test_pinned_tenants_survive_pressure(self):
        reg = serve.IndexRegistry(budget_bytes=300, headroom_frac=0.0)
        reg.admit("pinned", object(), size_bytes=200, pinned=True)
        with pytest.raises(serve.AdmissionError):
            reg.admit("big", object(), size_bytes=200)
        assert reg.get("pinned").state == "warming"

    def test_oversized_tenant_refused_outright(self):
        reg = serve.IndexRegistry(budget_bytes=100, headroom_frac=0.1)
        with pytest.raises(serve.AdmissionError, match="usable budget"):
            reg.admit("big", object(), size_bytes=95)

    def test_readmit_replaces(self):
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        reg = serve.IndexRegistry(budget_bytes=1000, headroom_frac=0.0)
        reg.admit("a", object(), size_bytes=900)
        reg.admit("a", object(), size_bytes=800)  # replaces, must fit
        assert reg.resident_bytes() == 800
        c = _counters(mreg)
        assert c["serve.registry.evict{reason=replaced,tenant=a}"] == 1.0

    def test_failed_hot_swap_keeps_the_serving_tenant(self):
        """Review hardening: a replacement that cannot fit must refuse
        WITHOUT destroying the tenant it would have replaced — and
        without evicting any bystander."""
        reg = serve.IndexRegistry(budget_bytes=1000, headroom_frac=0.0)
        prod = object()
        reg.admit("prod", prod, size_bytes=600)
        reg.admit("pinned_other", object(), size_bytes=300, pinned=True)
        with pytest.raises(serve.AdmissionError):
            reg.admit("prod", object(), size_bytes=1100)  # > budget
        with pytest.raises(serve.AdmissionError):
            # fits the budget alone, but not beside the pinned
            # bystander even after the prior's bytes come back
            reg.admit("prod", object(), size_bytes=800)
        t = reg.get("prod")
        assert t.state in ("warming", "serving") and t.index is prod
        assert reg.get("pinned_other").state == "warming"

    def test_mark_evicted_releases_residency(self):
        """Review hardening: mark(name, 'evicted') must drop the index
        and count the eviction exactly like evict() — a terminal
        tenant must never pin HBM that resident_bytes() stopped
        counting."""
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        reg = serve.IndexRegistry(budget_bytes=1000, headroom_frac=0.0)
        t = reg.admit("a", object(), size_bytes=400)
        reg.mark("a", "evicted")
        assert t.index is None and t.state == "evicted"
        assert reg.resident_bytes() == 0
        c = _counters(mreg)
        assert c["serve.registry.evict{reason=manual,tenant=a}"] == 1.0

    def test_failed_tenant_drops_index_and_refuses(self):
        reg = serve.IndexRegistry(budget_bytes=1000)
        reg.admit("a", object(), size_bytes=10)
        reg.mark("a", "failed")
        assert reg.resident_bytes() == 0
        with pytest.raises(serve.TenantUnknown):
            reg.get("a")

    def test_admit_faultpoint_is_armed(self):
        faults.install_plan({"faults": [
            {"site": "serve.registry.admit", "kind": "error",
             "times": 1}]})
        reg = serve.IndexRegistry(budget_bytes=1000)
        with pytest.raises(faults.FaultInjected):
            reg.admit("a", object(), size_bytes=10)
        reg.admit("a", object(), size_bytes=10)  # plan exhausted

    def test_describe_snapshot(self):
        reg = serve.IndexRegistry(budget_bytes=1000)
        reg.admit("a", object(), size_bytes=10)
        d = reg.describe()
        assert d["resident_bytes"] == 10
        assert d["tenants"][0]["name"] == "a"


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

class TestServer:
    def test_single_query_parity_with_direct_search(self, data, pq_index):
        """A served result equals the direct search's: padding to a
        bucket must not change any real row (per-query independence)."""
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=8, linger_s=0.001, default_slo_s=None))
        with srv:
            d, i = srv.search("pq", data[7], 10)
        d_ref, i_ref = ivf_pq.search(pq_index, jnp.asarray(data[7:8]),
                                     10, PQ_PARAMS)
        np.testing.assert_array_equal(i, np.asarray(i_ref)[0])
        np.testing.assert_allclose(d, np.asarray(d_ref)[0], rtol=1e-5,
                                   atol=1e-5)
        assert reg.get("pq").state == "serving"  # warmup marked it

    def test_coalesced_batch_matches_per_query(self, data, pq_index):
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=8, linger_s=0.05, default_slo_s=None))
        with srv:
            futs = [srv.submit("pq", data[j], 10) for j in range(8)]
            got = [f.result(timeout=30) for f in futs]
        d_ref, i_ref = ivf_pq.search(pq_index, jnp.asarray(data[:8]),
                                     10, PQ_PARAMS)
        for j, (d, i) in enumerate(got):
            np.testing.assert_array_equal(i, np.asarray(i_ref)[j])
        c = _counters(mreg)
        assert c["serve.requests{tenant=pq}"] == 8.0
        snap = mreg.snapshot()["histograms"]
        assert snap["serve.batch_fill"]["count"] >= 1
        assert snap["serve.latency_s"]["count"] == 8

    def test_full_queue_sheds_typed_never_hangs(self, pq_index):
        """The load-shedding contract: a bounded queue full of stalled
        work REJECTS new arrivals with ShedError(queue_full) — and
        every accepted request still terminates."""
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        # stall every dispatch so the queue cannot drain
        faults.install_plan({"faults": [
            {"site": "serve.dispatch", "kind": "sleep", "sleep_s": 0.2,
             "times": 0}]})
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=2, queue_depth=4, linger_s=0.0,
            default_slo_s=None, drain_s=10.0))
        q = np.zeros(DIM, np.float32)
        shed = []
        futs = []
        with srv:
            for _ in range(12):
                try:
                    futs.append(srv.submit("pq", q, 10))
                except serve.ShedError as e:
                    shed.append(e)
            # accepted work must terminate (results, not hangs)
            for f in futs:
                f.result(timeout=30)
        assert shed and all(e.reason == "queue_full" for e in shed)
        c = _counters(mreg)
        assert c["serve.shed{reason=queue_full}"] == len(shed)

    def test_expired_queue_deadline_is_shed_not_dispatched(self, pq_index):
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=1, linger_s=0.0, default_slo_s=None,
            drain_s=10.0))
        q = np.zeros(DIM, np.float32)
        with srv:
            # armed AFTER warmup so the one-shot stall hits the first
            # real dispatch; max_batch=1 serializes the two requests —
            # the second's 10 ms budget dies in the queue behind it
            faults.install_plan({"faults": [
                {"site": "serve.dispatch", "kind": "sleep",
                 "sleep_s": 0.25, "times": 1}]})
            slow = srv.submit("pq", q, 10, slo_s=None)
            doomed = srv.submit("pq", q, 10, slo_s=0.01)
            with pytest.raises(serve.DeadlineExceeded):
                doomed.result(timeout=30)
            slow.result(timeout=30)
        c = _counters(mreg)
        assert c["serve.shed{reason=deadline}"] >= 1.0
        assert c["serve.deadline_missed"] >= 1.0

    def test_injected_oom_mid_batch_walks_ladder_exact_results(
            self, data, pq_index):
        """The ISSUE's named chaos case: an OOM mid-batch walks the
        degrade ladder (halve_batch) and the served results are EXACT
        — identical to the same batch served without any fault."""
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=8, linger_s=0.05, default_slo_s=None))
        with srv:
            futs = [srv.submit("pq", data[j], 10) for j in range(8)]
            clean = [f.result(timeout=30) for f in futs]
            mreg = MetricsRegistry()
            obs.enable(registry=mreg, hbm=False)
            faults.install_plan({"faults": [
                {"site": "ivf_pq.search", "kind": "oom", "times": 1}]})
            futs = [srv.submit("pq", data[j], 10) for j in range(8)]
            degraded = [f.result(timeout=30) for f in futs]
        for (dc, ic), (dd, idg) in zip(clean, degraded):
            np.testing.assert_array_equal(ic, idg)
            np.testing.assert_allclose(dc, dd, rtol=1e-5, atol=1e-5)
        c = _counters(mreg)
        assert c.get("degrade.steps{from=native,"
                     "reason=resource_exhausted,site=ivf_pq.search,"
                     "to=halve_batch}", 0) >= 1, c
        assert c.get("faults.fired{kind=oom,site=ivf_pq.search}",
                     0) >= 1, c
        # the ladder fired during dispatch: health says so
        assert reg.get("pq").state == "degraded"

    def test_transient_dispatch_fault_is_retried(self, data, pq_index):
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=2, linger_s=0.0, default_slo_s=None))
        with srv:
            faults.install_plan({"faults": [
                {"site": "ivf_pq.search", "kind": "error", "times": 1}]})
            d, i = srv.search("pq", data[0], 10, timeout_s=30)
        assert i.shape == (10,)
        c = _counters(mreg)
        assert c.get("retry.recovered{site=serve.dispatch}", 0) >= 1, c

    def test_unknown_tenant_is_typed(self, pq_index):
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg)
        with pytest.raises(serve.TenantUnknown):
            srv.submit("ghost", np.zeros(DIM, np.float32))
        # review hardening: a bogus client-supplied name must not mint
        # a permanent labeled counter series (unbounded cardinality)
        assert "serve.requests{tenant=ghost}" not in _counters(mreg)

    def test_warmup_failure_marks_failed_and_serves_the_rest(
            self, data, pq_index, flat_index):
        """Review hardening: one tenant that cannot warm (every dispatch
        OOMs through an exhausted ladder) is marked failed — residency
        released, submits typed — while the healthy tenant warms and
        serves."""
        reg = _registry_with(pq_index, flat_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=2, linger_s=0.001, default_slo_s=None))
        faults.install_plan({"faults": [
            {"site": "ivf_pq.search", "kind": "oom", "times": 0}]})
        try:
            srv.start()
        finally:
            faults.clear_plan()
        try:
            assert reg.tenants()[0].state == "failed"  # pq
            assert reg.get("flat").state == "serving"
            with pytest.raises(serve.TenantUnknown) as ei:
                srv.submit("pq", data[0], 10)
            assert ei.value.state == "failed"
            _, ids = srv.search("flat", data[0], 10)
            assert ids.shape == (10,)
        finally:
            srv.stop()

    def test_steps_seen_is_thread_local(self):
        """Review hardening: another thread's ladder moves must not
        bump this thread's bracket counter (a concurrent tenant's
        degradation would falsely mark THIS dispatch's tenant)."""
        import threading

        before = degrade.steps_seen()
        t = threading.Thread(target=lambda: degrade.note_step(
            "other-thread", "native", "halve_batch", "test"))
        t.start()
        t.join()
        assert degrade.steps_seen() == before
        degrade.note_step("this-thread", "native", "halve_batch", "test")
        assert degrade.steps_seen() == before + 1

    def test_submit_before_start_sheds_not_running(self, pq_index):
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg)
        with pytest.raises(serve.ShedError) as ei:
            srv.submit("pq", np.zeros(DIM, np.float32))
        assert ei.value.reason == "not_running"

    def test_stop_sheds_queued_as_draining(self, pq_index):
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        faults.install_plan({"faults": [
            {"site": "serve.dispatch", "kind": "sleep", "sleep_s": 0.3,
             "times": 0}]})
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=1, queue_depth=32, linger_s=0.0,
            default_slo_s=None, drain_s=0.0))
        srv.start()
        q = np.zeros(DIM, np.float32)
        futs = [srv.submit("pq", q, 10) for _ in range(6)]
        srv.stop(drain=False)
        outcomes = []
        for f in futs:
            try:
                f.result(timeout=30)
                outcomes.append("ok")
            except serve.ShedError as e:
                outcomes.append(e.reason)
        assert "draining" in outcomes  # queued work shed, typed
        assert all(o in ("ok", "draining") for o in outcomes)

    def test_unwarmed_k_is_rejected_and_declared_ks_serve(self, data,
                                                          pq_index):
        """Review hardening: the k surface is closed at admission —
        submit() with an un-warmed k is a typed client error (it would
        recompile on the serving path), and every declared k serves."""
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        reg = serve.IndexRegistry(budget_bytes=1 << 30)
        reg.admit("pq", pq_index, params=PQ_PARAMS, default_k=10,
                  ks=[5, 10])
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=4, linger_s=0.001, default_slo_s=None))
        with srv:
            with pytest.raises(ValueError, match="warmed surface"):
                srv.submit("pq", data[0], k=7)
            d5, i5 = srv.search("pq", data[0], 5)
            d10, i10 = srv.search("pq", data[0], 10)
        assert i5.shape == (5,) and i10.shape == (10,)
        np.testing.assert_array_equal(i5, i10[:5])
        # every (bucket x k) shape warmed: 3 buckets x 2 ks
        c = _counters(mreg)
        assert c["serve.warmup{tenant=pq}"] == 6.0

    def test_degraded_marking_survives_recent_ring_saturation(
            self, data, pq_index):
        """Review hardening: the degraded-health signal compares the
        MONOTONIC degrade.steps_seen(), not the bounded recent ring —
        after 64+ process-wide ladder moves the ring saturates, and
        a dispatch-time walk must still mark the tenant."""
        for _ in range(70):  # saturate the ≤64-entry recent ring
            degrade.note_step("sat", "native", "halve_batch", "test")
        assert len(degrade.recent_steps()) == 64
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=4, linger_s=0.01, default_slo_s=None))
        with srv:
            faults.install_plan({"faults": [
                {"site": "ivf_pq.search", "kind": "oom", "times": 1}]})
            futs = [srv.submit("pq", data[j], 10) for j in range(4)]
            for f in futs:
                f.result(timeout=30)
        assert reg.get("pq").state == "degraded"

    def test_bad_query_shapes_rejected(self, pq_index):
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg).start(warmup=False)
        try:
            with pytest.raises(ValueError, match="one query vector"):
                srv.submit("pq", np.zeros((2, DIM), np.float32))
            with pytest.raises(ValueError, match="dim"):
                srv.submit("pq", np.zeros(DIM + 1, np.float32))
        finally:
            srv.stop()

    def test_mixed_tenants_coalesce_separately(self, data, pq_index,
                                               flat_index):
        reg = _registry_with(pq_index, flat_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=4, linger_s=0.02, default_slo_s=None))
        with srv:
            fp = [srv.submit("pq", data[j], 10) for j in range(4)]
            ff = [srv.submit("flat", data[j], 10) for j in range(4)]
            got_p = [f.result(timeout=30) for f in fp]
            got_f = [f.result(timeout=30) for f in ff]
        i_ref = np.asarray(ivf_flat.search(
            flat_index, jnp.asarray(data[:4]), 10,
            ivf_flat.SearchParams(n_probes=8))[1])
        for j, (_, i) in enumerate(got_f):
            np.testing.assert_array_equal(i, i_ref[j])
        assert all(i.shape == (10,) for _, i in got_p)

    @pytest.mark.parametrize("env_dir", [None, "/env/cache"])
    def test_compile_cache_dir_persists_every_bucket(self, monkeypatch,
                                                     tmp_path, env_dir):
        """``compile_cache_dir`` caches every program, however quick its
        compile, and yields to a set JAX_COMPILATION_CACHE_DIR."""
        import jax

        old_dir = jax.config.jax_compilation_cache_dir
        old_min = jax.config.jax_persistent_cache_min_compile_time_secs
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        if env_dir is not None:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        srv = serve.MicroBatchServer(
            serve.IndexRegistry(budget_bytes=1000), serve.ServerConfig(
                compile_cache_dir=str(tmp_path), default_slo_s=None))
        try:
            srv.start(warmup=False)
            srv.stop()
            assert jax.config.jax_compilation_cache_dir == (
                old_dir if env_dir else str(tmp_path))
            assert jax.config.jax_persistent_cache_min_compile_time_secs \
                == 0.0
        finally:
            jax.config.update("jax_compilation_cache_dir", old_dir)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              old_min)


# ---------------------------------------------------------------------------
# zero steady-state recompiles (the AOT-warmup contract)
# ---------------------------------------------------------------------------

class TestSteadyStateCompiles:
    def test_steady_state_is_recompile_free(self, data, pq_index):
        """After start(warmup=True), serving traffic across every
        bucket shape triggers ZERO backend compiles — the PR-3
        sanitizer turns an accidental retrace into a failure."""
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=8, linger_s=0.01, default_slo_s=None))
        with srv:
            # one extra settling pass: anything warmup's zeros-shaped
            # queries missed compiles here, outside the budget scope
            for j in range(3):
                srv.search("pq", data[j], 10)
            with sanitize.recompile_budget(0, what="steady-state serve"):
                for size in (1, 3, 8, 5, 2):
                    futs = [srv.submit("pq", data[j], 10)
                            for j in range(size)]
                    for f in futs:
                        f.result(timeout=30)


# ---------------------------------------------------------------------------
# loadgen
# ---------------------------------------------------------------------------

class TestLoadgen:
    def test_open_loop_step_records_curve_row(self, data, pq_index):
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=8, linger_s=0.002, default_slo_s=5.0))
        with srv:
            rows = loadgen.sweep(srv, "pq", data[:64], 10,
                                 offered_steps=[40.0], duration_s=0.4)
        (row,) = rows
        assert row["sent"] > 0 and row["completed"] > 0
        assert row["qps"] > 0
        assert row["latency_p50_s"] is not None
        assert row["latency_p99_s"] >= row["latency_p50_s"]
        assert row["errors"] == 0

    def test_record_stamps_provenance(self, data, pq_index):
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=8, default_slo_s=5.0))
        with srv:
            rows = loadgen.sweep(srv, "pq", data[:32], 10, [30.0],
                                 duration_s=0.3)
        rec = loadgen.record(rows, dataset="serve-test", tenant="pq",
                             k=10)
        (d,) = rec["detail"]
        assert d["dataset"] == "serve-test" and d["algo"] == "serve"
        assert d["search_param"] == {"offered_qps": 30.0, "k": 10}
        assert d["batch_size"] == 1
        assert d["env"]["jax"] and d["measured_at"]
        # benchdiff must be able to key the rows (the self-compare gate
        # in CI joins the committed baseline on exactly this)
        from tools import benchdiff

        keys = {benchdiff.row_key(r) for r in rec["detail"]}
        assert len(keys) == len(rec["detail"])

    def test_overload_step_sheds_and_says_so(self, data, pq_index):
        """Offered load far past capacity: the open-loop generator must
        SEE the shedding (a closed-loop one never would)."""
        reg = _registry_with(pq_index)
        srv = serve.MicroBatchServer(reg, serve.ServerConfig(
            max_batch=4, queue_depth=8, linger_s=0.0,
            default_slo_s=None, drain_s=10.0))
        faults.install_plan({"faults": [
            {"site": "serve.dispatch", "kind": "sleep", "sleep_s": 0.05,
             "times": 0}]})
        with srv:
            row = loadgen.run_step(srv, "pq", data[:32], 10,
                                   offered_qps=500.0, duration_s=0.4)
        assert row["shed"] > 0
        assert row["shed_reasons"].get("queue_full", 0) > 0
        assert row["sent"] >= row["completed"] + row["shed"]


# ---------------------------------------------------------------------------
# flight-dump chaos (SIGTERM mid-serving)
# ---------------------------------------------------------------------------

class TestServeFlightDump:
    @pytest.mark.slow  # subprocess builds its own index (~7 s); the CI
    # pytest + sanitize lanes run it — tier-1 keeps its 870 s headroom
    def test_sigterm_leaves_dump_with_serve_counters(self, tmp_path):
        """The ISSUE's named chaos case: a SIGTERM'd serving process
        leaves a parseable flight dump whose metrics snapshot carries
        the serve.* counter family."""
        code = f"""
import os, sys, time
sys.path.insert(0, {ROOT!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np, jax.numpy as jnp
from raft_tpu import obs, serve
from raft_tpu.obs import flight
from raft_tpu.neighbors import ivf_pq

obs.enable(hbm=False)
flight.install({str(tmp_path)!r}, every_s=0)
rng = np.random.default_rng(0)
x = rng.random((800, 16), dtype=np.float32)
idx = ivf_pq.build(jnp.asarray(x), ivf_pq.IndexParams(
    n_lists=8, pq_dim=8, seed=0, cache_reconstruction="never"))
reg = serve.IndexRegistry(budget_bytes=1 << 30)
reg.admit("t", idx, params=ivf_pq.SearchParams(
    n_probes=4, scan_mode="per_query"), default_k=5)
srv = serve.MicroBatchServer(reg, serve.ServerConfig(
    max_batch=4, linger_s=0.001, default_slo_s=5.0)).start()
srv.search("t", x[0], 5)
print("armed", flush=True)
while True:
    srv.search("t", x[0], 5)
    time.sleep(0.005)
"""
        p = subprocess.Popen([sys.executable, "-c", code],
                             stdout=subprocess.PIPE, text=True)
        assert p.stdout.readline().strip() == "armed"
        time.sleep(0.3)
        p.send_signal(signal.SIGTERM)
        p.wait(timeout=60)
        docs = []
        for name in sorted(os.listdir(tmp_path)):
            if name.startswith("flight_") and name.endswith(".json"):
                with open(os.path.join(str(tmp_path), name)) as f:
                    docs.append(json.load(f))
        dumps = [d for d in docs if d["reason"].startswith("signal")]
        assert dumps, [d["reason"] for d in docs]
        counters = dumps[0]["metrics"]["counters"]
        req = [k for k in counters if k.startswith("serve.requests")]
        assert req and counters[req[0]] >= 1, sorted(counters)
        assert any(k.startswith("serve.registry.admit")
                   for k in counters), sorted(counters)
        hists = dumps[0]["metrics"]["histograms"]
        assert "serve.latency_s" in hists


# ---------------------------------------------------------------------------
# ISSUE 15: request-scoped tracing + exposition through the server
# ---------------------------------------------------------------------------

class TestRequestTracing:
    def test_future_carries_trace_id(self, pq_index):
        from raft_tpu.obs import trace

        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001))
        with server:
            fut = server.submit("pq", np.zeros(DIM, np.float32), 10)
            fut.result(timeout=30)
        assert isinstance(fut.trace_id, str) and len(fut.trace_id) == 16

    def test_latency_exemplars_resolve_to_timelines(self, pq_index, data):
        from raft_tpu.obs import trace
        from raft_tpu.obs.metrics import exemplars_for_quantile

        prev = trace.set_buffer(trace.EventBuffer())
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False, events=True)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001))
        try:
            with server:
                for j in range(12):
                    server.search("pq", data[j], 10)
            lat = reg.snapshot()["histograms"]["serve.latency_s"]
            assert lat["count"] == 12
            ex = exemplars_for_quantile(lat, 0.99)
            assert ex, "p99 resolved to no exemplars"
            events = trace.get_buffer().snapshot()
            for e in ex:
                tid = e["trace_id"]
                mine = [ev for ev in events
                        if trace.event_matches_trace(ev, tid)]
                names = {ev["name"] for ev in mine}
                # the anchor event + the coalesced dispatch stages
                assert "serve.request" in names, names
                assert "serve.dispatch" in names, names
        finally:
            trace.set_buffer(prev)

    def test_request_event_details(self, pq_index, data):
        from raft_tpu.obs import trace

        prev = trace.set_buffer(trace.EventBuffer())
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False, events=True)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001))
        try:
            with server:
                fut = server.submit("pq", data[0], 10)
                fut.result(timeout=30)
            (ev,) = [e for e in trace.get_buffer().snapshot()
                     if e["name"] == "serve.request"
                     and e.get("args", {}).get("trace_id")
                     == fut.trace_id]
            args = ev["args"]
            assert args["outcome"] == "ok"
            assert args["tenant"] == "pq" and args["k"] == 10
            assert args["bucket"] >= 1 and 0 < args["fill"] <= 1.0
            assert args["queue_s"] >= 0.0
            assert ev["dur"] > 0
        finally:
            trace.set_buffer(prev)

    def test_ladder_walk_attributed_to_request(self, pq_index, data):
        from raft_tpu.obs import trace

        prev = trace.set_buffer(trace.EventBuffer())
        degrade.clear_recent()
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False, events=True)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001))
        try:
            with server:
                faults.install_plan({"faults": [
                    {"site": "ivf_pq.search", "kind": "oom",
                     "times": 1}]})
                fut = server.submit("pq", data[0], 10)
                fut.result(timeout=30)
                faults.clear_plan()
            steps = [s for s in degrade.recent_steps()
                     if s.get("site") == "ivf_pq.search"]
            assert steps, "no ladder move recorded"
            assert fut.trace_id in steps[-1].get("trace_ids", []), steps
            # and the zero-dur marker joined the request's timeline
            markers = [e for e in trace.get_buffer().snapshot()
                       if e["name"] == "degrade.step"
                       and trace.event_matches_trace(e, fut.trace_id)]
            assert markers
        finally:
            trace.set_buffer(prev)

    def test_shed_deadline_records_event(self, pq_index, data):
        from raft_tpu.obs import trace
        from raft_tpu.robust.retry import Deadline

        prev = trace.set_buffer(trace.EventBuffer())
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False, events=True)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001))
        try:
            with server:
                fut = server.submit("pq", data[0], 10, slo_s=1e-9)
                with pytest.raises(retry.DeadlineExceeded):
                    fut.result(timeout=30)
            evs = [e for e in trace.get_buffer().snapshot()
                   if e["name"] == "serve.request"
                   and trace.event_matches_trace(e, fut.trace_id)]
            assert evs and evs[0]["args"]["outcome"] == "shed_deadline"
        finally:
            trace.set_buffer(prev)


class TestServerExposition:
    def test_endpoint_lives_and_dies_with_server(self, pq_index, data):
        import urllib.request

        from raft_tpu.obs.expo import parse_prometheus

        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001,
                               expo_port=0))
        with server:
            assert server.expo is not None and server.expo.port > 0
            url = server.expo.url
            for j in range(3):
                server.search("pq", data[j], 10)
            text = urllib.request.urlopen(
                url + "/metrics", timeout=10).read().decode()
            fams = parse_prometheus(text)
            assert "raft_tpu_serve_requests" in fams
            assert "raft_tpu_serve_latency_s" in fams
            assert "raft_tpu_hbm_bytes_limit" in fams
            health = json.loads(urllib.request.urlopen(
                url + "/healthz", timeout=10).read())
            assert health["tenants"]["pq"] == "serving"
        assert server.expo is None  # stopped with the server
        with pytest.raises(Exception):
            urllib.request.urlopen(url + "/metrics", timeout=2)

    def test_budget_mirrored_even_when_obs_enabled_late(self, pq_index):
        """Registry built BEFORE obs.enable (the reverse of the CI
        smoke's order) must still expose hbm.bytes_limit once the
        server starts — the mirror re-fires at start()."""
        obs.disable()
        registry = _registry_with(pq_index)  # obs off: no init mirror
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        server = serve.MicroBatchServer(
            registry, serve.ServerConfig(max_batch=4, linger_s=0.001))
        with server:
            pass
        g = reg.snapshot()["gauges"]
        assert g.get("hbm.bytes_limit{source=admission}") == \
            float(registry.budget_bytes)

    def test_not_running_shed_records_anchor_event(self, pq_index,
                                                   data):
        from raft_tpu.obs import trace

        prev = trace.set_buffer(trace.EventBuffer())
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False, events=True)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001))
        try:
            with pytest.raises(serve.ShedError):
                server.submit("pq", data[0], 10)  # never started
            evs = [e for e in trace.get_buffer().snapshot()
                   if e["name"] == "serve.request"]
            assert evs and evs[-1]["args"]["outcome"] == \
                "shed_not_running"
        finally:
            trace.set_buffer(prev)

    def test_failed_bind_leaves_server_stopped(self, pq_index):
        """An expo port already in use must not leave a half-started
        server (live batcher, registered flight section, no endpoint,
        unrestartable) — start() tears back down and raises."""
        import socket

        from raft_tpu.obs import flight

        taken = socket.socket()
        taken.bind(("127.0.0.1", 0))
        port = taken.getsockname()[1]
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001,
                               expo_port=port))
        try:
            with pytest.raises(OSError):
                server.start()
            assert server.expo is None
            assert not server._running
            rec = flight.FlightRecorder("/tmp/raft_tpu_test_bind")
            body = rec.payload("test")
            rec.close()
            assert "serve_registry" not in body  # section cleared
            # the port freed -> the SAME server starts cleanly
            taken.close()
            with server:
                assert server.expo is not None
                assert server.expo.port == port
        finally:
            taken.close()
            flight.uninstall()

    def test_no_port_no_endpoint(self, pq_index):
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001))
        with server:
            assert server.expo is None

    def test_flight_section_registered_while_serving(self, pq_index):
        from raft_tpu.obs import flight

        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=4, linger_s=0.001))
        flight.uninstall()
        try:
            with server:
                rec = flight.FlightRecorder("/tmp/raft_tpu_test_sect")
                body = rec.payload("test")
                rec.close()
                tenants = {t["name"]: t["state"]
                           for t in body["serve_registry"]["tenants"]}
                assert tenants == {"pq": "serving"}
            rec = flight.FlightRecorder("/tmp/raft_tpu_test_sect")
            body = rec.payload("test")
            rec.close()
            assert "serve_registry" not in body  # cleared on stop
        finally:
            flight.uninstall()


class TestLoadgenExemplars:
    def test_run_step_returns_slow_trace_ids(self, pq_index, data):
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=8, linger_s=0.001))
        with server:
            row = loadgen.run_step(server, "pq", data[:64], 10,
                                   offered_qps=200.0, duration_s=0.5)
        assert row["completed"] > 0
        assert row["slow_trace_ids"], row
        assert all(len(t) == 16 for t in row["slow_trace_ids"])

    def test_record_notes_name_worst_p99_offenders(self):
        rows = [
            {"offered_qps": 100.0, "duration_s": 1.0, "sent": 10,
             "completed": 10, "shed": 0, "shed_reasons": {},
             "deadline_missed": 0, "errors": 0, "qps": 10.0,
             "latency_p50_s": 0.002, "latency_p99_s": 0.004,
             "latency_mean_s": 0.002, "slow_trace_ids": ["a" * 16]},
            {"offered_qps": 400.0, "duration_s": 1.0, "sent": 40,
             "completed": 40, "shed": 0, "shed_reasons": {},
             "deadline_missed": 0, "errors": 0, "qps": 40.0,
             "latency_p50_s": 0.004, "latency_p99_s": 0.090,
             "latency_mean_s": 0.01,
             "slow_trace_ids": ["b" * 16, "c" * 16]},
        ]
        rec = loadgen.record(rows, "ds", "pq", 10, note="base")
        assert "offered_qps=400.0" in rec["baseline_note"]
        assert "b" * 16 in rec["baseline_note"]
        assert rec["detail"][1]["slow_trace_ids"] == ["b" * 16, "c" * 16]

    def test_obsdump_slowest_renders_loadgen_offender(
            self, pq_index, data, tmp_path):
        from raft_tpu.obs import flight, trace

        prev = trace.set_buffer(trace.EventBuffer())
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False, events=True)
        server = serve.MicroBatchServer(
            _registry_with(pq_index),
            serve.ServerConfig(max_batch=8, linger_s=0.001))
        flight.uninstall()
        try:
            with server:
                row = loadgen.run_step(server, "pq", data[:64], 10,
                                       offered_qps=200.0,
                                       duration_s=0.5)
                rec = flight.FlightRecorder(str(tmp_path))
                path = rec.dump("test")
                rec.close()
            from tools import obsdump

            out = obsdump.render(path, top=5, slowest=3)
            assert "slowest 3 requests" in out
            assert "serve.request" in out
            # the loadgen's named offenders appear in the drill-down
            assert any(t in out for t in row["slow_trace_ids"])
        finally:
            trace.set_buffer(prev)
            flight.uninstall()


class TestQualityPlane:
    """ISSUE 16: the online recall verifier wired through the server —
    sampled replays feed quality gauges off the hot path, the flight
    dump grows a "quality" section, /healthz carries the SLO doc, and
    /indexz serves per-tenant index health."""

    def _quality_server(self, flat_index, data, **cfg):
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        registry = serve.IndexRegistry(budget_bytes=1 << 30)
        registry.admit("flat", flat_index,
                       params=ivf_flat.SearchParams(n_probes=16),
                       default_k=10, dataset=data, recall_floor=0.2)
        server = serve.MicroBatchServer(
            registry, serve.ServerConfig(
                max_batch=8, linger_s=0.001, verify_sample=1.0,
                verify_rate_per_s=1e9, **cfg))
        return server, reg

    def _wait_gauge(self, reg, key, timeout=15.0):
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            g = reg.snapshot()["gauges"]
            if key in g:
                return g
            time.sleep(0.02)
        raise AssertionError(
            f"{key} never appeared; gauges: "
            f"{sorted(reg.snapshot()['gauges'])}")

    def test_verifier_feeds_recall_gauges(self, flat_index, data):
        server, reg = self._quality_server(flat_index, data)
        with server:
            assert server.verifier is not None
            for j in range(24):
                server.search("flat", data[j], 10)
            g = self._wait_gauge(reg, "quality.recall{k=10,tenant=flat}")
            recall = g["quality.recall{k=10,tenant=flat}"]
            lo = g["quality.recall_ci_low{k=10,tenant=flat}"]
            hi = g["quality.recall_ci_high{k=10,tenant=flat}"]
            assert 0.0 <= lo <= recall <= hi <= 1.0
            # exact self-queries over the admitted dataset: n_probes=16
            # of 16 lists is exhaustive, recall must be perfect
            assert recall == pytest.approx(1.0)
            snap = reg.snapshot()
            assert snap["counters"][
                "quality.verified{tenant=flat}"] >= 1.0
            hkey = [k for k in snap["histograms"]
                    if k.startswith("quality.recall_loss{")]
            assert hkey, sorted(snap["histograms"])
        assert server.verifier is None  # stopped with the server

    def test_flight_quality_section_while_serving(self, flat_index,
                                                  data):
        from raft_tpu.obs import flight

        server, reg = self._quality_server(flat_index, data)
        flight.uninstall()
        try:
            with server:
                for j in range(8):
                    server.search("flat", data[j], 10)
                self._wait_gauge(reg,
                                 "quality.recall{k=10,tenant=flat}")
                rec = flight.FlightRecorder("/tmp/raft_tpu_test_qsect")
                body = rec.payload("test")
                rec.close()
                q = body["quality"]
                assert q["verified_total"] >= 1
                assert "flat" in q["tenants"]
                assert q["verdicts"][0]["trace_id"]
            rec = flight.FlightRecorder("/tmp/raft_tpu_test_qsect")
            body = rec.payload("test")
            rec.close()
            assert "quality" not in body  # cleared on stop
        finally:
            flight.uninstall()

    def test_healthz_and_indexz_over_http(self, flat_index, data):
        import urllib.request

        server, reg = self._quality_server(flat_index, data,
                                           expo_port=0)
        with server:
            for j in range(8):
                server.search("flat", data[j], 10)
            self._wait_gauge(reg, "quality.recall{k=10,tenant=flat}")
            url = server.expo.url
            health = json.loads(urllib.request.urlopen(
                url + "/healthz", timeout=10).read())
            assert health["status"] == "ok"       # floor 0.2 well met
            assert "recall_floor_breached" in health["slo"]
            assert health["slo"]["recall_floor_breached"] == []
            idx = json.loads(urllib.request.urlopen(
                url + "/indexz", timeout=10).read())
            ten = idx["tenants"]["flat"]
            assert ten["recall_floor"] == 0.2
            assert ten["stats"]["lists"]["n_lists"] == 16
            assert "cv" in ten["stats"]["lists"]

    def test_no_verify_sample_no_verifier(self, flat_index, data):
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        registry = serve.IndexRegistry(budget_bytes=1 << 30)
        registry.admit("flat", flat_index,
                       params=ivf_flat.SearchParams(n_probes=8),
                       default_k=10, dataset=data)
        server = serve.MicroBatchServer(
            registry, serve.ServerConfig(max_batch=8, linger_s=0.001))
        with server:
            assert server.verifier is None
            assert server.slo is not None   # guardrails run regardless
            server.search("flat", data[0], 10)
        assert "quality.recall{k=10,tenant=flat}" not in \
            reg.snapshot()["gauges"]


# ---------------------------------------------------------------------------
# fleet router (ISSUE 19)
# ---------------------------------------------------------------------------

class TestFleetRouter:
    """Straggler-steered cross-pod routing: placement, the one Deadline
    across the hop, the chaos pod-kill leg with exact shed/degrade
    accounting, and the steering control loop over the PR-15 straggler
    table feed."""

    def _capture_pod(self, name, hosts=()):
        calls = []

        def fn(tenant, queries, k, deadline):
            calls.append((tenant, deadline))
            return np.zeros((len(queries), k)), np.zeros((len(queries), k),
                                                         np.int64)

        return serve.Pod(name, hosts=hosts, dispatch_fn=fn), calls

    def test_placement_modes(self, pq_index):
        regs = [serve.IndexRegistry(budget_bytes=1 << 30) for _ in range(2)]
        router = serve.FleetRouter([
            serve.Pod("a", registry=regs[0]),
            serve.Pod("b", registry=regs[1])])
        assert sorted(router.place("hot", pq_index, hot=True,
                                   params=PQ_PARAMS)) == ["a", "b"]
        assert len(router.place("big", pq_index, sharded=True,
                                params=PQ_PARAMS)) == 1
        # single placement balances onto the emptier pod
        single = router.place("small", pq_index, params=PQ_PARAMS)
        assert len(single) == 1
        counts = {p.name: len(p.registry.resident()) for p in router.pods}
        assert abs(counts["a"] - counts["b"]) <= 1

    def test_straggler_feed_steers_dispatch(self):
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        pod_a, calls_a = self._capture_pod("a", hosts=("hostA",))
        pod_b, calls_b = self._capture_pod("b", hosts=("hostB",))
        router = serve.FleetRouter([pod_a, pod_b])
        q = np.zeros((2, 4), np.float32)
        router.dispatch("t", q, 3)
        router.dispatch("t", q, 3)
        assert calls_a and calls_b  # round-robin while both clean
        # PR-15 straggler-table shape: hostB lags 50% over fleet mean
        n = router.note_stragglers([
            {"collective": "comms.ring_topk", "slowest": "hostB",
             "skew_frac": 0.50},
            {"collective": "comms.allreduce", "slowest": "hostB",
             "skew_frac": 0.01}])   # below threshold: ignored
        assert n == 1
        before = len(calls_b)
        for _ in range(6):
            router.dispatch("t", q, 3)
        assert len(calls_b) == before   # steered away from hostB's pod
        c = _counters(mreg)
        assert c["serve.router.straggler{host=hostB}"] == 1.0
        assert c["serve.router.steer{away_from=hostB,reason=straggler}"] \
            >= 1.0
        assert router.describe()["pods"][1]["straggling"] is True

    def test_straggler_sighting_expires(self):
        now = [0.0]
        pod_a, calls_a = self._capture_pod("a", hosts=("hostA",))
        pod_b, calls_b = self._capture_pod("b", hosts=("hostB",))
        router = serve.FleetRouter(
            [pod_a, pod_b], serve.RouterPolicy(lag_window_s=60.0),
            clock=lambda: now[0])
        router.note_stragglers([{"slowest": "hostB", "skew_frac": 0.9}])
        assert router.straggling_hosts() == ["hostB"]
        now[0] = 61.0
        assert router.straggling_hosts() == []  # recovered host wins back
        q = np.zeros((1, 4), np.float32)
        for _ in range(4):
            router.dispatch("t", q, 3)
        assert calls_b

    def test_one_deadline_object_crosses_the_hop(self):
        pod, calls = self._capture_pod("a")
        router = serve.FleetRouter([pod])
        dl = retry.Deadline(5.0)
        router.dispatch("t", np.zeros((1, 4), np.float32), 3, deadline=dl)
        assert calls[0][1] is dl    # the ONE request budget, untouched

    def test_pod_kill_mid_storm_degraded_but_correct(self, data):
        # the ISSUE-19 chaos leg: two simulated pods on 4-device halves
        # of the 8-dev CPU mesh serve a replicated tenant; the DCN hop
        # to pod b dies mid-query-storm; every answered request must
        # equal the fault-free reference (degraded-but-correct from the
        # surviving pod) with exact failover accounting
        import jax
        from raft_tpu.parallel import make_mesh, sharded_knn

        devs = jax.devices()
        assert len(devs) >= 8, "CPU CI mesh must present 8 devices"
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)
        x = jnp.asarray(data[:1024])
        queries = np.asarray(data[:16], np.float32)
        k = 5

        def pod_fn(mesh):
            def fn(tenant, q, k_, deadline):
                v, i = sharded_knn(x, jnp.asarray(q), k_, mesh)
                return np.asarray(v), np.asarray(i)
            return fn

        mesh_a = make_mesh(devices=devs[:4])
        mesh_b = make_mesh(devices=devs[4:8])
        ref_v, ref_i = pod_fn(mesh_a)("t", queries, k, None)
        router = serve.FleetRouter([
            serve.Pod("a", hosts=("hostA",), dispatch_fn=pod_fn(mesh_a)),
            serve.Pod("b", hosts=("hostB",), dispatch_fn=pod_fn(mesh_b))])
        # pod b's DCN hop dies permanently at its 3rd crossing
        faults.install_plan({"faults": [
            {"site": "serve.router.hop.b", "kind": "error",
             "after": 3, "times": 0}]})
        answers = [router.dispatch("t", queries, k) for _ in range(10)]
        for v, i in answers:    # degraded-but-correct: every request
            np.testing.assert_array_equal(i, ref_i)
            np.testing.assert_allclose(v, ref_v, rtol=1e-5)
        assert not router.pods[1].healthy
        c = _counters(mreg)
        assert c["serve.router.pod_down{pod=b}"] == 1.0
        assert c["serve.router.degraded{reason=pod_lost}"] == 1.0
        assert c["serve.router.requests{tenant=t}"] == 10.0
        assert "serve.router.shed{reason=pod_unhealthy}" not in c
        # now the whole fleet dies: the refusal is typed, counted once
        faults.install_plan({"faults": [
            {"site": "serve.router.hop.a", "kind": "error", "times": 0}]})
        with pytest.raises(serve.ShedError) as exc:
            router.dispatch("t", queries, k)
        assert exc.value.reason == "pod_unhealthy"
        c = _counters(mreg)
        assert c["serve.router.shed{reason=pod_unhealthy}"] == 1.0
        assert c["serve.router.pod_down{pod=a}"] == 1.0

    def test_request_scoped_refusals_propagate_not_pod_down(self):
        mreg = MetricsRegistry()
        obs.enable(registry=mreg, hbm=False)

        def fn(tenant, q, k, deadline):
            raise retry.DeadlineExceeded("serve.dispatch",
                                         retry.Deadline(0.0))

        router = serve.FleetRouter([serve.Pod("a", dispatch_fn=fn)])
        with pytest.raises(retry.DeadlineExceeded):
            router.dispatch("t", np.zeros((1, 4), np.float32), 3)
        assert router.pods[0].healthy    # the request's problem
        assert "serve.router.pod_down{pod=a}" not in _counters(mreg)

    def test_global_install_clear_races(self):
        pod, _ = self._capture_pod("a")
        r1 = serve.FleetRouter([pod])
        r2 = serve.FleetRouter([pod])
        assert serve.set_router(r1) is None
        assert serve.get_router() is r1
        serve.clear_router(r2)            # stale teardown: no-op
        assert serve.get_router() is r1
        serve.clear_router(r1)
        assert serve.get_router() is None
