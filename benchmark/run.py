#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run. Set-up makes the configuration's data on the
device from the seed, builds the index through the program's public
entry, and warms the shapes the cell's traffic uses; then the window
measures for ``--seconds``. With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics, read from the
trace by the readers under ``metrics/``.

After the window (and after the device's peak memory is read and the
program's state freed) the plain reference in ``reference.py`` scores
every answer's recall and checks a sample of them (``check.py``):
``correct`` and the numbers compared, each beside its limit, close the
result line and standard error.

The last line of standard output is the result, a JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``. Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero before any
work and prints no result. JAX's compilation cache is kept in the
checkout's ``.cache/jax``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".cache", "jax")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, datagen, drivers, reference, stats  # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402
from benchmark.manifest import Bench  # noqa: E402


def require_tpu(chips: int):
    """The cell's devices; exits non-zero on any platform but a TPU, or
    with fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found platform "
                         f"{platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chips; JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def _phase(name: str, t_start: float) -> None:
    """One line of set-up on standard error: seconds since the start."""
    print(f"setup {name} at {time.perf_counter() - t_start:.3f} s",
          file=sys.stderr, flush=True)


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _profile(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def _end_to_end(window: drivers.Window, recall: float, setup_s: float):
    span = window.t_last - window.t0
    values = {"setup_s": setup_s, "recall_at_10": recall}
    if window.answered and span > 0:
        values["qps"] = window.answered / span
    if window.latency_s:
        values["p99_ms"] = 1e3 * stats.percentile(window.latency_s, 99)
    return values


def run_cell(bench: Bench, cell_name: str, seed: int, seconds: float,
             traced: bool, devices, t_start: float) -> dict:
    import jax

    cell = bench.cell(cell_name)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    limits = bench.limits(cell_name)
    system = bench.system(cfg["system"])
    k = cfg["search"]["k"]

    _phase("program imported", t_start)  # with the system's file
    base, queries = datagen.make(cfg["data"], seed)
    _phase("data made", t_start)
    t = time.perf_counter()
    index = jax.block_until_ready(system.build(cfg, base))
    build_s = time.perf_counter() - t
    _phase("index built", t_start)
    session = drivers.prepare(traffic, cfg, system, index, base, queries,
                              seed)
    _phase("shapes warmed", t_start)
    readers = {}
    summary = obs_registry = ctx = None
    try:
        if traced:
            readers = {m["name"]: bench.reader(m["name"])
                       for m in bench.metrics(cell_name, trace=True)}
            if any(getattr(r, "NEEDS_OBS", False) for r in readers.values()):
                from raft_tpu import obs

                obs_registry = obs.MetricsRegistry()
                obs.enable(registry=obs_registry, hbm=False)
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        setup_s = time.perf_counter() - t_start
        if traced:
            _profile(log_dir)
        try:
            window = session.run(seconds)
        finally:
            if traced:
                jax.profiler.stop_trace()
                if obs_registry is not None:
                    from raft_tpu import obs

                    obs.disable()
        memory_peak = _peak_bytes(devices)
        if traced:
            try:
                summary = trace_mod.read_dir(log_dir)
            finally:
                shutil.rmtree(log_dir, ignore_errors=True)
            ctx = types.SimpleNamespace(
                cell=cell, config=cfg, traffic=traffic, window=window,
                index=index, queries=queries, session=session,
                trace=summary, build_s=build_s,
                obs=obs_registry.snapshot() if obs_registry else None,
                peaks=functools.partial(bench.peaks,
                                        devices[0].device_kind))
            layer = {}
            for name, reader in readers.items():
                value = reader.read(ctx)
                if value is not None:
                    layer[name] = value
    finally:
        session.close()
    del session, index, ctx

    # the reference, once the window has closed and the state is freed
    t_ref = time.perf_counter()
    _, ref_ids = reference.exact_topk(base, queries, k)
    recall = check.recall(window.ids, ref_ids[window.query_idx])
    pick = check.sample(window.answered, seed)
    q_rows = jax.numpy.asarray(queries)[window.query_idx[pick]]
    ref_rows = reference.row_distances(base, q_rows, window.ids[pick])
    numbers = {"missing": window.failed,
               "bad_ids": check.bad_ids(window.ids, cfg["data"]["n"]),
               "dist_gap": check.dist_gap(window.dist[pick], ref_rows),
               "recall_miss": 1.0 - recall}
    correct, checks = check.judge(numbers, limits["limits"])
    print(f"reference and check {time.perf_counter() - t_ref:.3f} s",
          file=sys.stderr)

    if traced:
        wanted = bench.metrics(cell_name, trace=True)
        values = layer
    else:
        wanted = bench.metrics(cell_name, trace=False)
        values = _end_to_end(window, recall, setup_s)
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if m["name"] in values}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": window.attempted,
           "failed": window.failed, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(),
                            "idle_gaps": summary.top_gaps()}
    out["checks"] = checks
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, require=require_tpu, bench=None, t_start=None):
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    bench = bench or Bench(ROOT)
    cell = bench.cell(args.workload)
    _phase("harness imported", t_start)
    devices = require(cell["chips"])
    _phase("devices found", t_start)
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), devices, t_start)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return out


def _use_compile_cache() -> None:
    """Keep every compiled program in the checkout's fixed cache
    directory, so that a run after the first finds them all.

    The variable is set before JAX is imported, and the program's
    ``core.compile_cache`` then takes the same directory. A
    ``JAX_COMPILATION_CACHE_DIR`` inherited from the environment is
    overridden: it may be a fixed path shared by every checkout on the
    machine, and the benchmark's cache belongs to its own checkout. So
    is an inherited size limit, whose eviction takes a file lock and
    writes an access time on every read. The TPU runtime's own logs,
    which default to a fixed path under /tmp, are turned off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


if __name__ == "__main__":
    _use_compile_cache()
    main(t_start=T_PROCESS)
