"""Cost attribution (raft_tpu.obs.prof): version-tolerant Compiled
accessors, the device peak table, roofline classification, gauge
recording, the programmatic profiler bracket, and the bench runner's
cost columns (ISSUE 9 tentpole)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu import obs
from raft_tpu.obs import prof
from raft_tpu.obs.metrics import MetricsRegistry


@pytest.fixture(autouse=True)
def _obs_clean():
    yield
    obs.disable()
    obs.get_registry().reset()


class TestPeakTable:
    def test_known_kinds_map_to_their_entries(self):
        assert prof.peak_for_kind("TPU v4").name == "v4"
        assert prof.peak_for_kind("TPU v5e").name == "v5e"
        assert prof.peak_for_kind("TPU v5 lite").name == "v5e"
        assert prof.peak_for_kind("TPU v5p").name == "v5p"
        assert prof.peak_for_kind("cpu").name == "cpu"

    def test_unknown_kind_degrades_to_cpu_placeholder(self):
        # an unknown kind has no peaks: it raises instead of borrowing the
        # CPU placeholder, which only the CPU backend's own kind gets
        assert prof.peak_for_kind("cpu").placeholder
        for kind in ("", None, "TPU v9", "TPU v9 hyperpod", "gpu"):
            with pytest.raises(ValueError, match="device kind"):
                prof.peak_for_kind(kind)
            if kind is not None:  # None means "device 0's kind"
                with pytest.raises(ValueError, match="device kind"):
                    prof.interconnect_peak(kind)

    def test_ridge_is_flops_over_bw(self):
        for peak in prof.DEVICE_PEAKS.values():
            assert peak.ridge == pytest.approx(peak.flops / peak.hbm_bw)

    def test_device_peak_never_raises(self):
        # real device 0 (CPU mesh) maps to the CPU entry; a device whose
        # backend fails to report its kind raises rather than reading as
        # a CPU
        assert prof.device_peak().name == "cpu"
        assert prof.interconnect_peak().name == "cpu"

        class Broken:
            @property
            def device_kind(self):
                raise RuntimeError("backend gone")

        with pytest.raises(RuntimeError, match="backend gone"):
            prof.device_peak(Broken())


class TestVersionTolerantAccessors:
    def test_cost_analysis_dict_and_list_shapes(self):
        class AsDict:
            def cost_analysis(self):
                return {"flops": 10.0, "bytes accessed": 4.0, "other": "x"}

        class AsList:
            def cost_analysis(self):
                return [{"flops": 10.0, "bytes accessed": 4.0}]

        for compiled in (AsDict(), AsList()):
            ca = prof.cost_analysis(compiled)
            assert ca == {"flops": 10.0, "bytes accessed": 4.0}

    def test_cost_analysis_degrades_to_empty(self):
        class Raises:
            def cost_analysis(self):
                raise NotImplementedError

        class NoneShape:
            def cost_analysis(self):
                return None

        class EmptyList:
            def cost_analysis(self):
                return []

        for compiled in (Raises(), NoneShape(), EmptyList(), object()):
            assert prof.cost_analysis(compiled) == {}

    def test_memory_analysis_object_and_dict_shapes(self):
        class Stats:
            argument_size_in_bytes = 256
            output_size_in_bytes = 128
            temp_size_in_bytes = 64

        class Holder:
            def memory_analysis(self):
                return Stats()

        ma = prof.memory_analysis(Holder())
        assert ma["argument_size_in_bytes"] == 256
        assert ma["temp_size_in_bytes"] == 64

        class AsDict:
            def memory_analysis(self):
                return {"temp_size_in_bytes": 7}

        assert prof.memory_analysis(AsDict()) == {"temp_size_in_bytes": 7}
        assert prof.memory_analysis(object()) == {}


class TestAnalyze:
    def test_real_matmul_yields_roofline_fields(self):
        n = 256
        x = jnp.ones((n, n), jnp.float32)
        cost = prof.analyze_jit(lambda a: a @ a, x)
        assert cost is not None
        assert cost.flops and cost.flops > 0
        assert cost.bytes_accessed and cost.bytes_accessed > 0
        assert cost.arithmetic_intensity == pytest.approx(
            cost.flops / cost.bytes_accessed)
        assert cost.bound in ("memory", "compute")
        assert cost.ridge > 0 and cost.peak_bw > 0 and cost.peak_flops > 0

    def test_elapsed_attribution_sets_achieved_fracs(self):
        x = jnp.ones((64, 64), jnp.float32)
        cost = prof.analyze_jit(lambda a: a @ a, x, elapsed_s=1e-3)
        assert cost.achieved_bw_frac == pytest.approx(
            (cost.bytes_accessed / 1e-3) / cost.peak_bw)
        assert cost.achieved_flops_frac == pytest.approx(
            (cost.flops / 1e-3) / cost.peak_flops)
        # no elapsed → fracs stay None
        cost2 = prof.analyze_jit(lambda a: a @ a, x)
        assert cost2.achieved_bw_frac is None
        assert cost2.attribute_elapsed(None).achieved_bw_frac is None
        assert cost2.attribute_elapsed(0.0).achieved_bw_frac is None

    def test_untraceable_callable_returns_none(self):
        def hostile(a):
            if float(a[0, 0]) > 0:  # host sync on a tracer
                return a
            return -a

        assert prof.analyze_jit(hostile, jnp.ones((2, 2))) is None

    def test_as_row_columns(self):
        x = jnp.ones((64, 64), jnp.float32)
        row = prof.analyze_jit(lambda a: a @ a, x,
                               elapsed_s=1e-3).as_row()
        assert set(row) >= {"flops", "bytes_accessed", "bound",
                            "arith_intensity", "achieved_bw_frac"}
        assert row["bound"] in ("memory", "compute")

    def test_bound_classification_against_ridge(self):
        peak = prof.DEVICE_PEAKS["cpu"]

        class Fake:
            def __init__(self, flops, bts):
                self._c = {"flops": flops, "bytes accessed": bts}

            def cost_analysis(self):
                return self._c

            def memory_analysis(self):
                return None

        lo = prof.analyze_compiled(Fake(1.0, 1e6))   # AI « ridge
        hi = prof.analyze_compiled(Fake(1e12, 1.0))  # AI » ridge
        assert lo.bound == "memory" and hi.bound == "compute"
        assert lo.arithmetic_intensity < peak.ridge < \
            hi.arithmetic_intensity


class TestRecord:
    def test_gauges_land_with_program_label(self):
        reg = MetricsRegistry()
        cost = prof.ProgramCost(
            flops=100.0, bytes_accessed=50.0, arithmetic_intensity=2.0,
            bound="memory", peak_flops=1e9, peak_bw=1e8, ridge=10.0,
        ).attribute_elapsed(1e-3)
        prof.record(cost, registry=reg, program="p1")
        g = reg.snapshot()["gauges"]
        assert g["prof.flops{program=p1}"] == 100.0
        assert g["prof.bytes{program=p1}"] == 50.0
        assert g["prof.arith_intensity{program=p1}"] == 2.0
        assert g["prof.bound{bound=memory,program=p1}"] == 1.0
        assert g["prof.achieved_bw_frac{program=p1}"] == pytest.approx(
            (50.0 / 1e-3) / 1e8)

    def test_record_sanitizes_label_hostile_program_names(self):
        # the bench context embeds a search-param dict repr; the
        # registry's name{k=v,...} rendering has no escaping, so , { }
        # must be mapped out or parse_key chokes downstream
        from tools.obsdump import parse_key

        reg = MetricsRegistry()
        prof.record(prof.ProgramCost(flops=1.0), registry=reg,
                    program="ivf_pq.n1024 {'n_probes': 8, 'k': 10}")
        (key,) = reg.snapshot()["gauges"]
        name, labels = parse_key(key)
        assert name == "prof.flops"
        assert labels == {
            "program": "ivf_pq.n1024 ('n_probes': 8; 'k': 10)"}

    def test_record_skips_missing_fields(self):
        reg = MetricsRegistry()
        prof.record(prof.ProgramCost(), registry=reg, program="empty")
        assert reg.snapshot()["gauges"] == {}

    def test_record_defaults_to_live_obs_registry(self):
        reg = MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        prof.record(prof.ProgramCost(flops=1.0), program="d")
        assert reg.snapshot()["gauges"]["prof.flops{program=d}"] == 1.0


class TestCapture:
    @pytest.mark.slow  # full capture bracket; double_start keeps a tier-1 capture arm (tier-1 budget)
    def test_bracket_runs_and_degrades(self, tmp_path):
        cap = prof.capture(str(tmp_path / "xprof"))
        assert not cap.active
        with cap as c:
            # CPU backends may or may not support profiling — either
            # the capture armed, or it degraded with the error recorded
            assert c.active or c.error is not None
            jnp.ones((8, 8)).block_until_ready()
        assert not cap.active
        # stop() after stop is a no-op
        assert cap.stop() is None

    def test_double_start_is_idempotent(self, tmp_path):
        cap = prof.capture(str(tmp_path / "x"))
        cap.start()
        state = (cap.active, cap.error)
        cap.start()
        assert (cap.active, cap.error) == state
        cap.stop()

    def test_env_default_logdir(self, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_XPROF_DIR", "/tmp/envdir")
        assert prof.capture().logdir == "/tmp/envdir"


@pytest.mark.slow
class TestBenchRunnerCostColumns:
    """The acceptance shape: CPU smoke bench rows carry non-null
    flops/bytes_accessed/bound (and env provenance) when the OBS
    capture runs. Marked slow (a live build + OBS capture); the CI
    obs-smoke step asserts the same columns on the real smoke record,
    and the full pytest lane there includes slow tests."""

    @pytest.fixture()
    def rows(self, monkeypatch):
        from raft_tpu.bench import runner

        monkeypatch.setenv("RAFT_TPU_BENCH_OBS", "1")
        monkeypatch.setenv("RAFT_TPU_BENCH_OBS_REPS", "2")
        cfg = {
            "dataset": {"name": "prof-smoke", "n": 1500, "dim": 32,
                        "n_queries": 80, "metric": "sqeuclidean"},
            "k": 8, "batch_size": 10_000,
            "index": [{"name": "ivf_flat.n8", "algo": "ivf_flat",
                       "build_param": {"n_lists": 8},
                       "search_params": [{"n_probes": 4}]}],
        }
        return runner.run_config(cfg, verbose=False)

    def test_rows_carry_cost_and_env(self, rows):
        assert rows, "smoke config produced no rows"
        r = rows[0]
        assert r.cost is not None
        assert r.cost["flops"] and r.cost["flops"] > 0
        assert r.cost["bytes_accessed"] and r.cost["bytes_accessed"] > 0
        assert r.cost["bound"] in ("memory", "compute")
        assert r.cost["achieved_bw_frac"] > 0
        assert r.env is not None
        assert r.env["jax"] == jax.__version__
        assert r.env["device_count"] == len(jax.devices())
        assert r.env["device_kind"] is not None

    def test_environment_stamp_is_cached_and_complete(self):
        from raft_tpu.bench import runner

        env = runner.environment_stamp()
        assert env is runner.environment_stamp()  # cached
        for key in ("jax", "jaxlib", "libtpu", "backend", "device_kind",
                    "device_count", "local_device_count", "mesh_shape"):
            assert key in env
