"""Tests of the benchmark harness (``benchmark/``) on the CPU.

The whole command runs here at a tiny size, dispatching as on a TPU
with every Pallas kernel in interpret mode; the platform check is
steered by the test. The rest checks the pieces the harness's numbers
rest on: discovery of cells, mixes and metrics from files alone, the
trace reduction on a small recorded trace, the roofline work counts,
exact percentiles, the open-loop timing from the due time, the faults
and the control that ``correct`` has to catch, and the refusal of any
platform but a TPU.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import (check, datagen, drivers, reference, run,  # noqa: E402
                       stats, trace, work)
from benchmark.manifest import Bench  # noqa: E402

TINY_DATA = dict(n=2048, dim=16, n_queries=128)
TINY_INDEX = {"n_lists": 8, "pq_dim": 4}
TINY_TRAFFIC = {"batch10k": {"batch": 128}, "served": {"rate_per_s": 100}}


def _cpu(chips):
    return jax.devices()[:chips]


#: The IVF cells, which BENCHMARK.json leaves out until the IVF build
#: holds on the chip: their configurations, systems, mixes and readers
#: are files of the benchmark, run here from the tests' own manifest
#: entries (metric name -> the cells it is read in).
IVF_CELLS = {"sift1m-ivfpq.batch10k": "batch10k",
             "sift1m-ivfflat.batch10k": "batch10k",
             "sift1m-ivfpq.served": "served",
             "sift1m-ivfflat.batch10": "batch10"}
IVF_METRICS = {
    "qps": ["sift1m-ivfpq.batch10k", "sift1m-ivfflat.batch10k"],
    "p99_ms": ["sift1m-ivfpq.served", "sift1m-ivfflat.batch10"],
    "idle_share.throughput": ["sift1m-ivfpq.batch10k",
                              "sift1m-ivfflat.batch10k"],
    "idle_share.latency": ["sift1m-ivfpq.served", "sift1m-ivfflat.batch10"],
    "ivf_pq_scan_roofline": ["sift1m-ivfpq.batch10k"],
    "ivf_flat_scan_roofline": ["sift1m-ivfflat.batch10k"],
    "serve.batch_fill": ["sift1m-ivfpq.served"],
    "loadgen_late_ms": ["sift1m-ivfpq.served"]}
IVF_NEW_METRICS = {  # name: (unit, better, source, layer, moves)
    "ivf_pq_scan_roofline": ("%", "higher", "device_trace", "kernels", "qps"),
    "ivf_flat_scan_roofline": ("%", "higher", "device_trace", "kernels",
                               "qps"),
    "serve.batch_fill": ("%", "higher", "program_counter",
                         "serving front end", "p99_ms"),
    "loadgen_late_ms": ("ms", "lower", "host_clock",
                        "load generator (benchmark)", "p99_ms")}
IVF_LIMITS = {"missing": 0, "bad_ids": 0, "recall_miss": 0.1}


def _with_ivf_cells(manifest, dist_gap, bench_dir):
    """``manifest`` with the IVF cells, their metrics and limits."""
    for config in ("sift1m-ivfpq", "sift1m-ivfflat"):
        manifest["configs"].append({
            "name": config, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmark/configs/{config}.json"})
    for cell, traffic in IVF_CELLS.items():
        manifest["workloads"].append({
            "name": cell, "config": cell.split(".")[0], "traffic": traffic,
            "chips": 1, "why": "test"})
        _dump(os.path.join(bench_dir, "limits", cell + ".json"),
              {"limits": {**IVF_LIMITS, "dist_gap": dist_gap}})
    for name, (unit, better, source, layer, moves) in IVF_NEW_METRICS.items():
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": []})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += IVF_METRICS.get(metric["name"], [])


def make_tree(dst):
    """A copy of the benchmark at a tiny size under ``dst``, with the
    IVF cells in its manifest."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    dist_gap = Bench(ROOT).limits(manifest["workloads"][0]["name"])[
        "limits"]["dist_gap"]
    _with_ivf_cells(manifest, dist_gap, os.path.join(dst, "benchmark"))
    for entry in manifest["configs"]:
        path = os.path.join(dst, entry["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg["data"].update(TINY_DATA)
        cfg["index"].update({key: val for key, val in TINY_INDEX.items()
                             if key in cfg["index"]})
        if "n_probes" in cfg["search"]:
            cfg["search"]["n_probes"] = 4
        _dump(path, cfg)
    for name, change in TINY_TRAFFIC.items():
        path = os.path.join(dst, "benchmark", "traffic", name + ".json")
        with open(path) as f:
            _dump(path, {**json.load(f), **change})
    _dump(os.path.join(dst, "BENCHMARK.json"), manifest)
    return dst


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Dispatch as on a TPU; every kernel in TPU interpret mode (process
    global, so the server's batcher thread sees it too)."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    pltpu.set_tpu_interpret_mode()
    try:
        yield
    finally:
        pltpu.set_tpu_interpret_mode(None)
        jax.clear_caches()


def _run(tree, cell, traced=0, seed=2**31 + 7, seconds=1.0):
    return run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(traced)],
                    require=_cpu, bench=Bench(tree))


# -- the whole command ---------------------------------------------------

@pytest.mark.parametrize("cell,traced", [
    ("sift1m-bruteforce.batch10k", 1), ("sift1m-bruteforce.batch10", 0),
    ("sift1m-ivfpq.batch10k", 0), ("sift1m-ivfflat.batch10k", 1),
    ("sift1m-ivfpq.served", 1), ("sift1m-ivfflat.batch10", 0)])
def test_cell_runs_end_to_end(tree, tpu_dispatch, capsys, cell, traced):
    out = _run(tree, cell, traced)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(out))
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    bench = Bench(tree)
    if traced:
        # no TPU plane in a CPU trace: the device readers find nothing
        want = {m["name"] for m in bench.metrics(cell, trace=True)
                if m["source"] != "device_trace"}
        assert set(out["device"]) >= {"busy_s", "window_s"}
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        want = {m["name"] for m in bench.metrics(cell, trace=False)}
    assert set(out["metrics"]) == want
    assert out["metrics"][next(iter(want))]["unit"]
    assert out["checks"]["dist_gap"]["value"] < 1e-4
    assert out["checks"]["bad_ids"]["value"] == 0
    if not traced:
        assert 0.5 < out["metrics"]["recall_at_10"]["value"] <= 1.0


@pytest.mark.parametrize("only_paths", [False, True])
def test_refuses_any_platform_but_a_tpu(tmp_path, only_paths):
    """Exits non-zero, naming the platform, and prints no result; also
    from a directory holding only the manifest and the benchmark's
    paths."""
    cwd = ROOT
    if only_paths:
        cwd = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), cwd)
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            for path in json.load(f)["paths"]:
                shutil.copytree(os.path.join(ROOT, path),
                                os.path.join(cwd, path),
                                ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sift1m-bruteforce.batch10k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert '"correct"' not in p.stdout


# -- discovery from files ------------------------------------------------

def test_new_cell_mix_config_and_metric_from_files_alone(tmp_path):
    root = make_tree(str(tmp_path))
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs", "sift1m-ivfflat.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-flat-probes2"
    cfg["search"]["n_probes"] = 2
    _dump(os.path.join(bdir, "configs", "tiny-flat-probes2.json"), cfg)
    _dump(os.path.join(bdir, "traffic", "batch32.json"),
          {"kind": "closed_loop", "batch": 32})
    _dump(os.path.join(bdir, "limits", "tiny-flat-probes2.batch32.json"),
          {"limits": {"missing": 0, "bad_ids": 0, "dist_gap": 1e-4,
                      "recall_miss": 0.1}})
    with open(os.path.join(bdir, "metrics", "calls_in_window.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx.window.batches)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-flat-probes2", "source": "test",
        "file": "benchmark/configs/tiny-flat-probes2.json", "reduced": [],
        "why": "test"})
    manifest["workloads"].append({
        "name": "tiny-flat-probes2.batch32", "config": "tiny-flat-probes2",
        "traffic": "batch32", "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "calls_in_window", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "load generator (benchmark)",
        "moves": "qps", "workloads": ["tiny-flat-probes2.batch32"]})
    _dump(os.path.join(root, "BENCHMARK.json"), manifest)

    bench = Bench(root)
    assert bench.config("tiny-flat-probes2")["search"]["n_probes"] == 2
    assert bench.traffic("batch32")["batch"] == 32
    assert "calls_in_window" in {
        m["name"] for m in bench.metrics("tiny-flat-probes2.batch32", True)}
    assert "calls_in_window" not in {
        m["name"] for m in bench.metrics("sift1m-ivfpq.served", True)}
    out = _run(root, "tiny-flat-probes2.batch32", traced=1)
    assert out["correct"] is True
    assert out["metrics"]["calls_in_window"]["value"] >= 1
    assert out["metrics"]["build_s"]["value"] > 0


def test_unknown_names_raise(tree):
    bench = Bench(tree)
    # a split metric without a reader of its own reads its stem's
    assert (bench.reader("idle_share.latency")
            is bench.reader("idle_share.throughput")
            is bench.reader("idle_share"))
    for lookup in (bench.cell, bench.config, bench.traffic, bench.reader,
                   bench.system):
        with pytest.raises((KeyError, FileNotFoundError)):
            lookup("no-such-name")


def test_peak_table_knows_v5e_and_refuses_an_unknown_kind():
    bench = Bench(ROOT)
    v5e = bench.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in the peak table"):
        bench.peaks("cpu")


# -- the trace reduction -------------------------------------------------

def _xspace():
    """A small recorded trace in XSpace text form, laid out as a TPU's:
    one chip whose op events are named by their HLO text (two overlap,
    one spills past the window, one names the kernel's output as an
    operand) inside two program runs, and a host thread."""
    def ev(meta, start_ns, dur_ns):
        return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000}"
                f" duration_ps: {dur_ns * 1000} }}")

    def meta(key, name):
        name = name.replace('"', '\\"')
        return f'event_metadata {{ key: {key} value {{ id: {key} name: "{name}" }} }}'

    device_ops = " ".join([
        ev(1, 1000, 2000), ev(2, 2500, 1000),   # overlapping
        ev(1, 6000, 1000), ev(3, 9500, 2000),   # the last past the window
    ])
    modules = " ".join([ev(4, 900, 3000), ev(5, 5900, 6000)])
    host = " ".join([ev(1, 500, 9500), ev(2, 800, 3000), ev(2, 5000, 4000),
                     ev(3, 4100, 800)])
    kernel = ('%segmented_scan_topk.1 = (f32[8,128]{1,0:T(8,128)}, '
              's32[8,128]{1,0:T(8,128)}) custom-call(f32[8,128]{1,0} %p), '
              'custom_call_target="tpu_custom_call"')
    fusion = ('%fusion.2 = s32[64]{0:T(1024)S(1)} fusion(s32[8,128]'
              '{1,0:T(8,128)} %jit_segmented_scan_topk_.5), kind=kLoop')
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {device_ops} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {modules} }}
  {meta(1, kernel)}
  {meta(2, fusion)}
  {meta(3, "%copy.3 = f32[8]{0} copy(f32[8]{0} %a)")}
  {meta(4, "jit__search(123)")}
  {meta(5, "jit__refine(456)")}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 5 name: "python" timestamp_ns: 0 {host} }}
  {meta(1, "bench.window")}
  {meta(2, "bench.call")}
  {meta(3, "bench.wait")}
}}
"""


def test_trace_reduction_on_a_recorded_trace():
    from jax.profiler import ProfileData

    s = trace.summarize(ProfileData.from_text_proto(_xspace()))
    assert s.window == pytest.approx((500e-9, 10000e-9))
    # busy: [1000, 3500] + [6000, 7000] + [9500, 10000] (clipped)
    assert s.busy_s == pytest.approx(4000e-9)
    assert s.idle_share == pytest.approx(1 - 4000 / 9500)
    # the kernel's own events only, not the fusion that reads its output
    assert s.kernel_seconds(["segmented_scan_topk"]) == pytest.approx(
        3000e-9)
    assert s.kernel_seconds(["jit_segmented_scan_topk_"]) == 0
    # program runs [900, 3900] and [5900, 11900], clipped to the window
    assert s.module_seconds(["jit__search"]) == pytest.approx(3000e-9)
    assert s.module_seconds(["jit__refine"]) == pytest.approx(4100e-9)
    assert s.top_ops()[0] == [
        "jit__search/segmented_scan_topk.1 (custom-call)",
        pytest.approx(2000e-9)]
    assert {name for name, _ in s.top_ops()} == {
        "jit__search/segmented_scan_topk.1 (custom-call)",
        "jit__refine/segmented_scan_topk.1 (custom-call)",
        "jit__search/fusion.2 (fusion)", "jit__refine/copy.3 (copy)"}
    gaps = {name: secs for name, secs in s.top_gaps()}
    # gaps [500, 1000], [3500, 6000] and [7000, 9500]: their midpoints
    # lie in the window alone, in bench.wait (inside the window) and in
    # the second bench.call; the innermost annotation wins
    assert gaps == {"bench.call": pytest.approx(2500e-9),
                    "bench.window": pytest.approx(500e-9),
                    "bench.wait": pytest.approx(2500e-9)}


def test_hlo_names_and_opcodes():
    assert trace.parse_hlo(
        "%sort.1 = (f32[10,256]{1,0:T(8,128)}, s32[10,256]{1,0}) "
        "sort(f32[10,256]{1,0:T(8,128)S(1)} %a), dimensions={1}") == (
            "sort.1", "sort")
    assert trace.parse_hlo("copy.3") == ("copy.3", "")


def test_union_and_complement():
    assert trace.union([(3, 4), (0, 2), (1, 3), (5, 5)]) == [(0, 4)]
    assert trace.complement([(1, 2), (3, 4)], 0, 5) == [
        (0, 1), (2, 3), (4, 5)]


# -- roofline work counts ------------------------------------------------

def test_roofline_work_counts_by_hand():
    centers = np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0]], np.float32)
    sizes = np.array([5, 7, 11])
    queries = np.array([[1.0, 0.0], [9.0, 1.0]], np.float32)
    probes = work.probe_lists(centers, queries, 2)
    assert [sorted(p) for p in probes.tolist()] == [[0, 1], [0, 1]]
    w = work.call_work(probes, sizes, row_bytes=8, ops_per_pair=4, dim=2,
                       k=3)
    assert w["pairs"] == 2 * (5 + 7)
    assert w["ops"] == 24 * 4
    # lists 0 and 1 once: 12 rows x (8 + 4) B, queries 2x2x4 B, results
    # 2x3x8 B
    assert w["bytes"] == 12 * 12 + 16 + 48
    share, bound = work.roofline_share(ops=2e12, nbytes=1e9, seconds=0.1,
                                       peak_ops=197e12, peak_bytes=819e9)
    assert bound == "compute"
    assert share == pytest.approx(100 * (2e12 / 197e12) / 0.1)
    assert work.roofline_share(1.0, 819e9, 2.0, 197e12, 819e9) == (
        pytest.approx(50.0), "memory")
    # exact search: 10 queries x 100 rows x 8 dims; rows and norms read
    # once (100 x 36 B), queries 10 x 32 B, results 10 x 3 x 8 B
    assert work.knn_work(m=10, n=100, dim=8, k=3) == {
        "ops": 2 * 10 * 100 * 8, "bytes": 3600 + 320 + 240}


# -- statistics and the load generator ---------------------------------

def test_percentile_is_exact_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 99) == 99
    assert stats.percentile(values, 100) == 100
    assert stats.percentile([5.0, 1.0, 3.0], 50) == 3.0
    assert stats.percentile([2.5], 99) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([], 99)


class _StallingServer:
    """Answers at once, but its second submit stalls the generator."""

    class config:  # noqa: N801 — read as server.config.max_batch
        max_batch = 2

    def __init__(self, stall_s):
        self.stall_s = stall_s
        self.calls = 0

    def submit(self, tenant, query, slo_s=None):
        self.calls += 1
        if self.calls == 4 + 2:  # the second submit after the settle burst
            time.sleep(self.stall_s)
        fut = Future()
        fut.set_result((np.zeros(3, np.float32), np.arange(3)))
        return fut

    def stop(self):
        pass


def test_open_loop_times_from_the_due_time():
    queries = np.zeros((8, 4), np.float32)
    loop = drivers.OpenLoop(_StallingServer(0.3), "t", queries,
                            {"rate_per_s": 40.0}, seed=3)
    w = loop.run(1.0)
    assert w.attempted == 40 and w.failed == 0 and w.answered == 40
    due, _ = loop.schedule(1.0)
    late = np.asarray(w.late_s)
    lat = np.asarray(w.latency_s)
    # requests due during the stall go out late, and their latency,
    # taken from the due time, carries the wait
    assert late.max() >= 0.2
    stalled = late >= 0.05
    assert stalled.sum() >= 2
    assert np.all(lat[stalled] >= late[stalled])
    assert np.all(lat >= late - 1e-6)
    assert 0.0 < due[0] and due[-1] < 1.0 and np.all(np.diff(due) > 0)
    # every seed gets the same number of requests
    other = drivers.OpenLoop(_StallingServer(0.0), "t", queries,
                             {"rate_per_s": 40.0}, seed=4)
    assert len(other.schedule(1.0)[0]) == len(due)


def test_sweep_reports_each_offered_rate(tree, tpu_dispatch, capsys):
    from benchmark import sweep

    rows = sweep.main(["--workload", "sift1m-ivfpq.served", "--seed", "9",
                       "--rates", "20,40", "--seconds", "0.5"],
                      require=_cpu, bench=Bench(tree))
    assert [r["offered_per_s"] for r in rows] == [20.0, 40.0]
    for row in rows:
        assert row["failed"] == 0 and row["completed_per_s"] > 0
        assert 0 < row["p50_ms"] <= row["p99_ms"]
    printed = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(line) for line in printed[-2:]] == rows


# -- what ``correct`` has to catch ---------------------------------------

def _altered(search):
    def wrapped(*a, **kw):
        d, i = search(*a, **kw)
        return d, i.at[:, 0].set((i[:, 0] + 1) % TINY_DATA["n"])
    return wrapped


def _half_left_out(search):
    def wrapped(*a, **kw):
        d, i = search(*a, **kw)
        half = i.shape[0] // 2
        return (d.at[half:].set(jnp.inf), i.at[half:].set(-1))
    return wrapped


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
@pytest.mark.parametrize("cell,module,entry", [
    ("sift1m-bruteforce.batch10k", "brute_force", "knn"),
    ("sift1m-ivfflat.batch10k", "ivf_flat", "search"),
    ("sift1m-ivfpq.served", "ivf_pq", "search_resilient")])
def test_a_broken_timed_path_is_not_correct(tree, monkeypatch, fault, cell,
                                            module, entry):
    import importlib

    mod = importlib.import_module("raft_tpu.neighbors." + module)
    monkeypatch.setattr(mod, entry, fault(getattr(mod, entry)))
    out = _run(tree, cell, seconds=0.5)
    assert out["correct"] is False


@pytest.mark.parametrize("cell", ["sift1m-bruteforce.batch10k",
                                  "sift1m-bruteforce.batch10"])
def test_a_select_that_drops_neighbours_is_not_correct(tree, monkeypatch,
                                                       cell):
    """The exact search with its per-tile cut at depth 1 (the fault the
    recall_miss limit was read from) returns exact distances of the
    wrong rows: dist_gap passes it and recall_miss fails it."""
    from benchmark import calibrate

    try:
        calibrate.depth1_select(monkeypatch.setattr, tile_rows=512)
        out = _run(tree, cell, seconds=0.5)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    checks = out["checks"]
    assert out["correct"] is False
    assert checks["dist_gap"]["value"] <= checks["dist_gap"]["limit"]
    assert checks["recall_miss"]["value"] > 10 * checks["recall_miss"]["limit"]


def test_control_fails_the_committed_limits():
    """The reference at three bf16 passes (a TPU's Precision.HIGH) in the
    program's place fails a committed limit of every cell, while the
    reference at the configuration's precision passes them all; real
    widths, fewer rows."""
    bench = Bench(ROOT)
    limits = {c["name"]: bench.limits(c["name"])["limits"]
              for c in bench.manifest["workloads"]}
    data = dict(bench.config("sift1m-bruteforce")["data"], n=20_000,
                n_queries=1024)
    for seed in (11, 12, 13):
        base, queries = datagen.make(data, seed)
        _, exact = reference.exact_topk(base, queries, 10)
        for precision, fails in (("bf16_3x", True), ("highest", False)):
            dist, ids = reference.exact_topk(base, queries, 10,
                                             precision=precision)
            numbers = {"dist_gap": check.dist_gap(
                dist, reference.row_distances(base, queries, ids)),
                "recall_miss": 1.0 - check.recall(ids, exact)}
            for cell, lim in limits.items():
                failed = any(numbers[k] > lim[k] for k in numbers)
                assert failed == fails, (seed, precision, cell, numbers)


def test_check_numbers():
    ids = np.array([[0, 1, 2], [3, 3, 9], [-1, 4, 5]])
    assert check.bad_ids(ids, n_rows=9) == 3   # the repeat, 9 and -1
    ref = np.array([[1.0, 2.0, np.nan]])
    assert check.dist_gap(np.array([[1.0, 2.2, 5.0]]), ref) == (
        pytest.approx(0.1))
    assert check.recall(np.array([[1, 2], [3, 4]]),
                        np.array([[2, 1], [3, 5]])) == 0.75
    ok, checks = check.judge({"a": 0, "b": 2.0}, {"a": 0, "b": 1.0})
    assert not ok and checks["b"] == {"value": 2.0, "limit": 1.0}
    assert not check.judge({"c": 0}, {})[0]
    assert check.judge({"a": 0}, {"a": 0}) == (True, {
        "a": {"value": 0, "limit": 0}})
    pick = check.sample(10_000, seed=5)
    assert len(pick) == check.SAMPLE_ANSWERS
    assert np.array_equal(pick, check.sample(10_000, seed=5))


def test_reference_is_exact():
    rng = np.random.default_rng(3)
    base = jnp.asarray(rng.standard_normal((3000, 16)), jnp.float32)
    queries = jnp.asarray(rng.standard_normal((20, 16)), jnp.float32)
    d64 = ((np.asarray(queries, np.float64)[:, None, :]
            - np.asarray(base, np.float64)[None]) ** 2).sum(-1)
    want = np.argsort(d64, axis=1, kind="stable")[:, :10]
    dist, ids = reference.exact_topk(base, queries, 10, q_tile=8,
                                     block=512)
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_allclose(
        reference.row_distances(base, queries, ids),
        np.take_along_axis(d64, want, axis=1), rtol=1e-5)
    np.testing.assert_allclose(dist, np.take_along_axis(d64, want, 1),
                               rtol=1e-4)


def test_data_from_a_large_seed_is_repeatable():
    data = dict(generator="hard_clusters", n=512, dim=8, n_queries=16,
                rows_per_cluster=24, sigma=0.45)
    a = datagen.make(data, 2**33 + 5)
    b = datagen.make(data, 2**33 + 5)
    c = datagen.make(data, 5)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    assert a[0].shape == (512, 8) and a[1].shape == (16, 8)


# -- the manifest keeps to its contract ------------------------------------

def test_manifest_names_only_files_that_exist():
    bench = Bench(ROOT)
    m = bench.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in m["configs"]]
    assert len(set(names)) == len(names)
    for cell in m["workloads"]:
        assert cell["config"] in names and cell["chips"] == 1
        assert len(cell["why"]) <= 200
        bench.traffic(cell["traffic"])
        assert set(bench.limits(cell["name"])["limits"]) == {
            "missing", "bad_ids", "dist_gap", "recall_miss"}
        bench.system(bench.config(cell["config"])["system"])
        reported = {x["name"] for x in bench.metrics(cell["name"], False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert bench.metrics(cell["name"], True)
    ends = {e["name"] for e in m["end_to_end"]}
    for metric in m["per_layer"]:
        assert metric["moves"] in ends
        assert hasattr(bench.reader(metric["name"]), "read")
        for cell in metric.get("workloads", []):
            assert metric["moves"] in {
                x["name"] for x in bench.metrics(cell, False)}
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
