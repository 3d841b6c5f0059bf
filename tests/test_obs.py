"""Observability layer: metrics registry, span timers, HBM telemetry,
and the no-overhead-when-disabled contract (see
docs/observability.md)."""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.core import tracing
from raft_tpu.neighbors import ivf_pq
from raft_tpu.obs import hbm
from raft_tpu.obs.metrics import MetricsRegistry


@pytest.fixture(autouse=True)
def _obs_clean():
    """Spans/registries are process-global state — leave none behind."""
    yield
    obs.disable()
    obs.get_registry().reset()


@pytest.fixture(scope="module")
def pq_index():
    rng = np.random.default_rng(0)
    x = rng.random((4000, 32), dtype=np.float32)
    q = rng.random((200, 32), dtype=np.float32)
    idx = ivf_pq.build(x, ivf_pq.IndexParams(
        n_lists=32, pq_dim=16, seed=0, cache_reconstruction="never"))
    return idx, jnp.asarray(q)


class TestMetricsRegistry:
    def test_counter_math_and_labels(self):
        r = MetricsRegistry()
        r.inc("reqs")
        r.inc("reqs", 2.5)
        r.inc("reqs", 1, labels={"algo": "ivf_pq"})
        r.inc("reqs", 2, labels={"algo": "ivf_pq"})
        snap = r.snapshot()
        assert snap["counters"]["reqs"] == 3.5
        assert snap["counters"]["reqs{algo=ivf_pq}"] == 3.0
        with pytest.raises(ValueError):
            r.counter("reqs").inc(-1)

    def test_label_order_is_canonical(self):
        r = MetricsRegistry()
        r.inc("c", 1, labels={"a": "1", "b": "2"})
        r.inc("c", 1, labels={"b": "2", "a": "1"})  # same series
        assert r.snapshot()["counters"]["c{a=1,b=2}"] == 2.0

    def test_gauge_set_and_max(self):
        r = MetricsRegistry()
        r.set("g", 5)
        r.set("g", 3)
        assert r.snapshot()["gauges"]["g"] == 3.0
        r.gauge("peak").max(10)
        r.gauge("peak").max(7)  # high-water keeps 10
        assert r.snapshot()["gauges"]["peak"] == 10.0

    def test_histogram_math(self):
        r = MetricsRegistry()
        h = r.histogram("lat", buckets=[0.01, 0.1, 1.0])
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        st = h.state()
        assert st["count"] == 4
        assert st["sum"] == pytest.approx(5.555)
        assert st["min"] == 0.005 and st["max"] == 5.0
        assert st["mean"] == pytest.approx(5.555 / 4)
        # cumulative buckets: ≤0.01 → 1, ≤0.1 → 2, ≤1.0 → 3, +inf → 4
        assert st["buckets"]["0.01"] == 1
        assert st["buckets"]["0.1"] == 2
        assert st["buckets"]["1.0"] == 3
        assert st["buckets"]["+inf"] == 4

    def test_counter_thread_safety(self):
        r = MetricsRegistry()

        def work():
            for _ in range(1000):
                r.inc("n")

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert r.snapshot()["counters"]["n"] == 8000.0

    def test_jsonl_round_trip(self, tmp_path):
        r = MetricsRegistry()
        r.inc("c", 2, labels={"x": "1"})
        r.set("g", 7.5)
        r.observe("h", 0.02)
        path = str(tmp_path / "metrics.jsonl")
        n = r.dump_jsonl(path, extra={"run": "t0"})
        assert n == 3
        rows = obs.load_jsonl(path)
        by = {(row["kind"], row["name"]): row for row in rows}
        assert by[("counter", "c")]["value"] == 2.0
        assert by[("counter", "c")]["labels"] == {"x": "1"}
        assert by[("gauge", "g")]["value"] == 7.5
        assert by[("histogram", "h")]["count"] == 1
        assert by[("histogram", "h")]["sum"] == pytest.approx(0.02)
        assert all(row["run"] == "t0" for row in rows)
        # appends (the bench writes one block per measured row)
        r.dump_jsonl(path)
        assert len(obs.load_jsonl(path)) == 6
        # every line is self-contained JSON
        with open(path) as f:
            for line in f:
                json.loads(line)

    def test_reset_and_set_registry(self):
        r = MetricsRegistry()
        r.inc("a")
        r.reset()
        assert r.snapshot() == {"counters": {}, "gauges": {},
                                "histograms": {}}
        prev = obs.set_registry(r)
        try:
            assert obs.get_registry() is r
        finally:
            obs.set_registry(prev)


class TestHistogramQuantileEdges:
    """Edge cases of ``Histogram.quantile`` / ``quantile_from_state`` —
    the values benchdiff's noise model reads off the recorded bench
    reps, so the degenerate shapes (empty, single sample, single
    bucket) must degrade predictably instead of interpolating junk."""

    def test_empty_histogram_returns_none(self):
        h = MetricsRegistry().histogram("h")
        assert h.quantile(0.5) is None
        assert obs.quantile_from_state(h.state(), 0.5) is None

    def test_single_sample_every_q_is_the_sample(self):
        h = MetricsRegistry().histogram("h", buckets=[1.0, 10.0])
        h.observe(3.5)
        for q in (0.0, 0.25, 0.5, 0.99, 1.0):
            # min/max clamping pins every quantile to the one value
            assert h.quantile(q) == pytest.approx(3.5)

    def test_single_bucket_histogram_clamps_to_observed_range(self):
        h = MetricsRegistry().histogram("h", buckets=[100.0])
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        # one giant bucket: interpolation alone would sweep [0, 100];
        # the min/max clamps keep estimates inside the data
        assert 1.0 <= h.quantile(0.5) <= 4.0
        assert h.quantile(0.0) == pytest.approx(1.0)
        assert h.quantile(1.0) == pytest.approx(4.0)

    def test_q_clamping_outside_unit_interval(self):
        h = MetricsRegistry().histogram("h", buckets=[1.0, 2.0, 4.0])
        for v in (0.5, 1.5, 3.0):
            h.observe(v)
        # q outside [0, 1] clamps to the endpoints rather than raising
        assert h.quantile(-0.5) == h.quantile(0.0)
        assert h.quantile(2.0) == h.quantile(1.0)
        assert h.quantile(0.0) == pytest.approx(0.5)
        assert h.quantile(1.0) == pytest.approx(3.0)

    def test_all_samples_in_overflow_bucket(self):
        h = MetricsRegistry().histogram("h", buckets=[1.0])
        h.observe(5.0)
        h.observe(7.0)
        # +inf bucket has no upper bound to interpolate against: the
        # estimate falls back to the observed max
        assert h.quantile(0.99) == pytest.approx(7.0)
        assert h.quantile(0.5) == pytest.approx(7.0)

    def test_state_without_buckets_degrades(self):
        # hand-built state (a flight dump from a foreign process might
        # carry a truncated histogram): no buckets → max fallback
        st = {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0,
              "mean": 2.0, "buckets": {}}
        assert obs.quantile_from_state(st, 0.5) == 3.0


class TestSpans:
    def test_nested_spans_dot_join(self):
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        with tracing.span("search"):
            with tracing.span("scan"):
                pass
            with tracing.span("scan"):
                pass
        obs.disable()
        h = reg.snapshot()["histograms"]
        assert h["span.search"]["count"] == 1
        assert h["span.search.scan"]["count"] == 2
        assert h["span.search.scan"]["sum"] >= 0

    def test_no_record_on_exception(self):
        # a raising block yields a truncated duration — it must not mix
        # into the same series as successful samples, and the stack must
        # still unwind
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        with pytest.raises(RuntimeError):
            with tracing.span("boom"):
                raise RuntimeError("x")
        obs.disable()
        assert "span.boom" not in reg.snapshot()["histograms"]
        assert obs.current_name() == ""

    def test_disabled_spans_record_nothing(self):
        assert not obs.enabled()
        with tracing.span("ghost") as sp:
            sp.attach(jnp.arange(3))
        assert "span.ghost" not in obs.get_registry().snapshot()["histograms"]

    def test_sync_mode_blocks_on_attached(self, monkeypatch):
        blocked = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: blocked.append(x) or real(x))
        reg = MetricsRegistry()
        obs.enable(sync=True, registry=reg, hbm=False)
        with tracing.span("s") as sp:
            sp.attach(jnp.arange(8) * 2)
        obs.disable()
        assert len(blocked) == 1
        assert reg.snapshot()["histograms"]["span.s"]["count"] == 1

    def test_spans_skip_under_jit_trace(self):
        reg = MetricsRegistry()
        obs.enable(sync=True, registry=reg, hbm=False)

        @jax.jit
        def f(x):
            # a span inside a traced function must not record (it would
            # measure trace time once) nor block on tracers
            with tracing.span("inside_jit") as sp:
                y = x * 2
                sp.attach(y)
                return y

        np.testing.assert_array_equal(np.asarray(f(jnp.arange(4))),
                                      [0, 2, 4, 6])
        obs.disable()
        assert "span.inside_jit" not in reg.snapshot()["histograms"]

    def test_traced_records_span_when_enabled(self):
        reg = MetricsRegistry()

        @tracing.traced("raft_tpu.test.traced_span")
        def f(x):
            return x + 1

        assert f(1) == 2  # disabled: no record
        obs.enable(registry=reg, hbm=False)
        assert f(1) == 2
        obs.disable()
        h = reg.snapshot()["histograms"]
        assert h["span.test.traced_span"]["count"] == 1


class TestHbm:
    def test_helpers_degrade_without_allocator_stats(self):
        # CPU backend reports nothing; all helpers must not raise
        stats = hbm.device_memory_stats()
        assert isinstance(stats, dict)
        assert hbm.bytes_limit(default=123) == (
            123 if "bytes_limit" not in stats else int(stats["bytes_limit"]))
        biu = hbm.bytes_in_use()
        assert biu is None or isinstance(biu, int)

    def test_sample_writes_gauges_only_when_reported(self):
        reg = MetricsRegistry()
        stats = hbm.sample(reg)
        gauges = reg.snapshot()["gauges"]
        if stats.get("bytes_in_use") is not None:
            assert gauges["hbm.bytes_in_use"] == stats["bytes_in_use"]
        else:
            assert "hbm.bytes_in_use" not in gauges

    def test_sample_covers_all_local_devices(self, monkeypatch):
        # sharded runs must see EVERY chip's HBM, not just device 0 —
        # fake a 2-device backend that reports allocator stats
        class FakeDev:
            def __init__(self, n):
                self._n = n

            def memory_stats(self):
                return {"bytes_in_use": 100 * self._n,
                        "peak_bytes_in_use": 200 * self._n,
                        "bytes_limit": 1000}

        monkeypatch.setattr(hbm, "_local_devices",
                            lambda: [FakeDev(1), FakeDev(2)])
        reg = MetricsRegistry()
        stats = hbm.sample(reg)
        assert stats["bytes_in_use"] == 100  # device 0's dict returned
        gauges = reg.snapshot()["gauges"]
        assert gauges["hbm.bytes_in_use{device=0}"] == 100
        assert gauges["hbm.bytes_in_use{device=1}"] == 200
        assert gauges["hbm.peak_bytes{device=1}"] == 400
        # unlabeled back-compat series mirrors device 0 (bench peak col)
        assert gauges["hbm.bytes_in_use"] == 100
        assert gauges["hbm.peak_bytes"] == 200

    def test_sample_mixed_reporting_devices(self, monkeypatch):
        # a device mid-outage (stats -> {}) must not hide the others
        class Dead:
            def memory_stats(self):
                raise RuntimeError("transport down")

        class Live:
            def memory_stats(self):
                return {"bytes_in_use": 7}

        monkeypatch.setattr(hbm, "_local_devices", lambda: [Dead(), Live()])
        reg = MetricsRegistry()
        stats = hbm.sample(reg)
        assert stats == {}  # device 0 degraded
        gauges = reg.snapshot()["gauges"]
        assert "hbm.bytes_in_use{device=0}" not in gauges
        assert gauges["hbm.bytes_in_use{device=1}"] == 7
        assert "hbm.bytes_in_use" not in gauges  # unlabeled = device 0

    def test_sample_records_counter_events(self, monkeypatch):
        from raft_tpu.obs import trace

        class Dev:
            def memory_stats(self):
                return {"bytes_in_use": 11, "peak_bytes_in_use": 13}

        monkeypatch.setattr(hbm, "_local_devices", lambda: [Dev()])
        buf = trace.EventBuffer()
        hbm.sample(MetricsRegistry(), events=buf)
        names = {e["name"] for e in buf.snapshot()}
        assert "hbm.bytes_in_use{device=0}" in names


class TestDeviceResourcesMetrics:
    def test_handle_hands_out_global_registry(self):
        from raft_tpu.core.resources import DeviceResources

        h = DeviceResources()
        assert h.metrics is obs.get_registry()
        mine = MetricsRegistry()
        h.set_metrics(mine)
        assert h.metrics is mine
        assert isinstance(h.memory_stats(), dict)

    def test_handle_follows_enable_registry_override(self):
        # handle metrics must land in the same sink spans record into,
        # including a temporary obs.enable(registry=...) override (the
        # bench's per-row capture)
        from raft_tpu.core.resources import DeviceResources

        h = DeviceResources()
        mine = MetricsRegistry()
        obs.enable(registry=mine, hbm=False)
        try:
            assert h.metrics is mine
        finally:
            obs.disable()
        assert h.metrics is obs.get_registry()

    def test_handle_follows_global_registry_swap(self):
        # regression: the handle must resolve the global registry per
        # access, not cache the one current at first read — otherwise
        # h.metrics goes stale after the bench swaps in a fresh registry
        from raft_tpu.core.resources import DeviceResources

        h = DeviceResources()
        assert h.metrics is obs.get_registry()  # read once (would cache)
        fresh = MetricsRegistry()
        prev = obs.set_registry(fresh)
        try:
            assert h.metrics is fresh
        finally:
            obs.set_registry(prev)


class TestEnvFlag:
    def test_falsy_strings_mean_off(self, monkeypatch):
        for v in ("0", "false", "False", "off", "no", ""):
            monkeypatch.setenv("RAFT_TPU_TEST_FLAG", v)
            assert not obs.env_flag("RAFT_TPU_TEST_FLAG"), v
        for v in ("1", "true", "yes", "on"):
            monkeypatch.setenv("RAFT_TPU_TEST_FLAG", v)
            assert obs.env_flag("RAFT_TPU_TEST_FLAG"), v
        monkeypatch.delenv("RAFT_TPU_TEST_FLAG")
        assert not obs.env_flag("RAFT_TPU_TEST_FLAG")


class TestSelectKDispatchCounter:
    def test_counts_dispatch_decisions(self):
        from raft_tpu.matrix.select_k import select_k

        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        select_k(jnp.arange(100.0).reshape(2, 50), 5)
        obs.disable()
        counters = reg.snapshot()["counters"]
        assert any(n.startswith("select_k.dispatch{") for n in counters), \
            counters


class TestNoOverheadWhenDisabled:
    """ISSUE 1 acceptance (extended by ISSUE 5 to the event recorder
    and the instrumented collectives): with observability disabled, the
    instrumented paths add no sync points, record no events, count no
    comm traffic, and cost <2% wall time."""

    def test_disabled_search_records_no_events(self, pq_index):
        # ISSUE 5: the event-recording hook in span.__exit__ must stay
        # behind the enable flag — a disabled search leaves the ring
        # buffer untouched
        from raft_tpu.obs import trace

        idx, q = pq_index
        assert not obs.enabled()
        buf = trace.EventBuffer()
        prev = trace.set_buffer(buf)
        try:
            ivf_pq.search(idx, q, 10,
                          ivf_pq.SearchParams(n_probes=8,
                                              scan_mode="per_query"))
        finally:
            trace.set_buffer(prev)
        assert len(buf) == 0

    def test_disabled_collectives_count_nothing(self):
        # ISSUE 5: instrumented comms must be free when obs is off —
        # no comms.* series appear anywhere, no events recorded
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from raft_tpu.obs import trace
        from raft_tpu.parallel import Comms, make_mesh

        assert not obs.enabled()
        mesh = make_mesh(axis_names=("shard",))
        comms = Comms("shard")
        buf = trace.EventBuffer()
        prev = trace.set_buffer(buf)
        try:
            out = shard_map(
                lambda v: comms.allgather(comms.allreduce(v)),
                mesh=mesh, in_specs=(P("shard"),),
                out_specs=P("shard", None), check_vma=False,
            )(jnp.arange(8, dtype=jnp.float32))
            jax.block_until_ready(out)
        finally:
            trace.set_buffer(prev)
        counters = obs.get_registry().snapshot()["counters"]
        assert not any(n.startswith("comms.") for n in counters), counters
        assert len(buf) == 0

    def test_no_block_until_ready_from_span_code(self, monkeypatch,
                                                 pq_index):
        idx, q = pq_index
        assert not obs.enabled()
        calls = []
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: calls.append(type(x)) or x)
        d, i = ivf_pq.search(idx, q, 10,
                             ivf_pq.SearchParams(n_probes=8,
                                                 scan_mode="per_query"))
        np.asarray(i)  # consume without block_until_ready
        assert calls == [], "span code introduced a sync point"

    def test_disabled_overhead_under_2pct(self, pq_index):
        idx, q = pq_index
        assert not obs.enabled()
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="per_query")
        ivf_pq.search(idx, q, 10, sp)  # warm the jit cache

        # cost of one disabled span enter/exit
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with tracing.span("overhead_probe"):
                pass
        per_span = (time.perf_counter() - t0) / n

        reps = 10
        t0 = time.perf_counter()
        for _ in range(reps):
            d, i = ivf_pq.search(idx, q, 10, sp)
        jax.block_until_ready(i)
        per_search = (time.perf_counter() - t0) / reps

        # the instrumented path opens a handful of spans per search;
        # 32 is a generous over-estimate
        assert 32 * per_span < 0.02 * per_search, (
            f"disabled span cost {per_span * 1e6:.2f}µs × 32 exceeds 2% "
            f"of a {per_search * 1e3:.2f}ms search")
