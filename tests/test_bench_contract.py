"""Driver-contract tests for bench.py's record machinery (no device
work): headline selection, the always-emits-JSON property under
SIGTERM, and the refusal to measure anything but a TPU. A record
printed only at the very end is lost when a time limit cuts the run.
"""
import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _row(dataset, algo, qps, recall, index="i"):
    return {"dataset": dataset, "algo": algo, "index": index,
            "qps": qps, "recall": recall, "build_s": 1.0,
            "search_param": {}}


def test_headline_prefers_recall_bar(bench):
    bench.STATE["detail"] = [
        _row("sift-1m-hard-synth", "ivf_flat", 200_000, 0.90, "fast"),
        _row("sift-1m-hard-synth", "ivf_flat", 70_000, 0.96, "good"),
        _row("sift-1m-hard-synth", "brute_force", 20_000, 1.0),
    ]
    p = bench._payload()
    assert p["metric"].startswith("ann_qps_at_recall95")
    assert p["value"] == 70_000 and p["best_algo"] == "good"


def test_headline_flags_missed_bar(bench):
    bench.STATE["detail"] = [
        _row("sift-1m-hard-synth", "ivf_flat", 200_000, 0.90)]
    assert bench._payload()["metric"] == \
        "ann_qps_below_recall_bar_hard1m_b10000_k10"


def test_headline_brute_force_only_is_not_ann(bench):
    bench.STATE["detail"] = [
        _row("sift-1m-hard-synth", "brute_force", 20_000, 1.0)]
    assert bench._payload()["metric"] == "brute_force_qps_hard1m_b10000_k10"


def test_sigterm_emits_record():
    # a real subprocess: SIGTERM mid-run must still print a JSON line
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import bench, time\n"
        "import signal\n"
        "signal.signal(signal.SIGTERM, bench._die)\n"
        "bench.STATE['detail'].append({'dataset': 'sift-1m-hard-synth',"
        " 'algo': 'ivf_flat', 'index': 'i', 'qps': 5.0, 'recall': 0.99,"
        " 'build_s': 1.0, 'search_param': {}})\n"
        "print('ready', flush=True)\n"
        "time.sleep(60)\n" % ROOT
    )
    p = subprocess.Popen([sys.executable, "-c", code],
                         stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "ready"
    p.send_signal(signal.SIGTERM)
    out, _ = p.communicate(timeout=30)
    payload = json.loads(out.strip().splitlines()[-1])
    assert payload["detail"][0]["qps"] == 5.0
    assert any("signal" in n for n in payload["notes"])


def test_sigterm_stamps_flight_dump_path(tmp_path):
    """ISSUE 5 acceptance: a bench run killed by SIGTERM leaves a
    flight dump whose path appears in the partial record's notes (the
    recorder only arms once raft_tpu is imported — as the runner legs
    do — so the child imports it before waiting)."""
    code = (
        "import sys, os; sys.path.insert(0, %r)\n"
        "os.environ['RAFT_TPU_FLIGHT_DIR'] = %r\n"
        "import bench, time, signal\n"
        "import raft_tpu  # the runner legs would have imported it\n"
        "bench._install_flight()\n"
        "signal.signal(signal.SIGTERM, bench._die)\n"
        "print('ready', flush=True)\n"
        "time.sleep(60)\n" % (ROOT, str(tmp_path))
    )
    p = subprocess.Popen([sys.executable, "-c", code],
                         stdout=subprocess.PIPE, text=True)
    for line in p.stdout:
        if line.strip() == "ready":
            break
    p.send_signal(signal.SIGTERM)
    out, _ = p.communicate(timeout=60)
    payload = json.loads(out.strip().splitlines()[-1])
    stamped = [n for n in payload["notes"] if n.startswith("flight dump: ")]
    assert stamped, payload["notes"]
    dump_path = stamped[0][len("flight dump: "):]
    assert os.path.dirname(dump_path) == str(tmp_path)
    with open(dump_path) as f:
        doc = json.load(f)
    assert doc["reason"].startswith("signal")
    assert "metrics" in doc and "events" in doc


class TestGistConf:
    """GIST-960 leg wiring (ISSUE 4 satellite: BASELINE config 4 has
    recorded zero rows in five rounds — the conf now lives in
    raft_tpu/bench/conf and this CPU-shaped smoke proves the wiring
    produces rows every CI round)."""

    CONF = os.path.join(ROOT, "raft_tpu", "bench", "conf", "gist-960.json")

    def _load(self):
        with open(self.CONF) as f:
            return json.load(f)

    def test_conf_schema(self):
        cfg = self._load()
        assert cfg["dataset"]["name"] == "gist-960-euclidean"
        assert cfg["k"] == 10
        algos = {i["algo"] for i in cfg["index"]}
        assert algos == {"cagra", "ivf_flat", "ivf_pq"}
        # BASELINE config 4: CAGRA graph_degree=64 on GIST-1M
        cagra = next(i for i in cfg["index"] if i["algo"] == "cagra")
        assert cagra["build_param"]["graph_degree"] == 64
        # ISSUE 11: the fp8-QLUT recall-delta legs — the lut_dtype
        # triple at FIXED search params, per dataset
        pq = next(i for i in cfg["index"] if i["algo"] == "ivf_pq")
        dtype_legs = [sp for sp in pq["search_params"]
                      if "lut_dtype" in sp]
        triple = [sp["lut_dtype"] for sp in dtype_legs]
        assert triple == ["float32", "bfloat16", "float8_e4m3"]
        fixed = [{k: v for k, v in sp.items() if k != "lut_dtype"}
                 for sp in dtype_legs]
        assert all(f == fixed[0] for f in fixed)
        # ISSUE 12: the filtered-search legs — the selectivity sweep on
        # the fused tier plus the 10% forced-fallback twin (leg_env
        # pins the pre-ISSUE-12 tier for the cliff comparison)
        filt = [sp for sp in pq["search_params"]
                if "filter_selectivity" in sp]
        fused = sorted(sp["filter_selectivity"] for sp in filt
                       if "leg_env" not in sp)
        assert fused == [0.01, 0.1, 0.5], filt
        forced = [sp for sp in filt if "leg_env" in sp]
        assert len(forced) == 1 and forced[0]["filter_selectivity"] == 0.1
        assert forced[0]["leg_env"] == {
            "RAFT_TPU_PALLAS_LUTSCAN": "never"}, forced

    @pytest.mark.slow  # full runner pass over every conf entry; the CI bench legs run the same smoke (tier-1 budget)
    def test_cpu_shaped_smoke(self):
        """Run the conf's index entries through the real runner on a
        tiny 960-d synthetic (the dataset dir is absent on CI): every
        entry must produce rows — the exact property the leg lacked."""
        from raft_tpu.bench import runner

        cfg = self._load()
        cfg["dataset"] = {"name": "gist-960-smoke", "n": 600, "dim": 960,
                          "n_queries": 40,
                          "metric": cfg["dataset"]["metric"]}
        cfg["batch_size"] = 40
        # CPU-shaped shrink of the build/search params only — the
        # wiring (algos, refine_ratio leg, runner plumbing) is what the
        # smoke exercises, not 1M-scale QPS
        for entry in cfg["index"]:
            if entry["algo"] == "cagra":
                entry["build_param"]["graph_degree"] = 8
                entry["search_params"] = [{"itopk_size": 16,
                                           "search_width": 4}]
                continue
            entry["build_param"]["n_lists"] = 8
            entry["build_param"].pop("spill", None)
            entry["build_param"].pop("list_size_cap_factor", None)
            if entry["algo"] == "ivf_pq":
                # keep the lut_dtype triple (the legs under test),
                # shrink everything else to CPU shape
                entry["build_param"]["pq_dim"] = 16
                entry["search_params"] = [
                    {"n_probes": 4, "scan_select": "approx",
                     "refine_ratio": 4, "lut_dtype": dt}
                    for dt in ("float32", "bfloat16", "float8_e4m3")]
            else:
                entry["search_params"] = [
                    {"n_probes": 4, "scan_select": "approx"},
                    {"n_probes": 4, "scan_select": "approx",
                     "refine_ratio": 4}]
        rows = runner.run_config(cfg, verbose=False)
        by_algo = {}
        for r in rows:
            by_algo.setdefault(r.algo, []).append(r)
        assert set(by_algo) == {"cagra", "ivf_flat", "ivf_pq"}, \
            by_algo.keys()
        assert len(by_algo["ivf_flat"]) == 2
        # one row per lut_dtype leg, recall recorded on each (the
        # recall-delta rows the fp8 default is judged by)
        assert len(by_algo["ivf_pq"]) == 3
        assert all(r.qps > 0 and 0.0 <= r.recall <= 1.0 for r in rows)
