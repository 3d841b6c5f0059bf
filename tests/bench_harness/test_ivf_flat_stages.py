"""Tests of the IVF-Flat search's per-stage readers
(``benchmark/ivf_flat_stages.py``, ``metrics/ivf_flat_*_ms.py``) on the
CPU.

The readers run on a synthetic trace summary whose ops carry the real
instruction names of the search program compiled here, for each of the
two programs ``ivf_flat.search`` picks, so the map they build from the
compiled program is the one that names the ops.
"""

import contextlib
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import ivf_flat_stages, scopes, trace  # noqa: E402
from benchmark.manifest import Bench  # noqa: E402
from raft_tpu.neighbors import ivf_flat  # noqa: E402

#: the route: (batch, per-query tile, program name on the trace). The
#: per-query program at one tile lets the compiler fuse the gather into
#: the contraction that reads it; over several tiles it stays an op of
#: its own
ROUTES = {"per_query": (8, 256, "jit__search_impl"),
          "per_query_tiled": (8, 4, "jit__search_impl"),
          "grouped": (256, 256, "jit__search_grouped")}
READERS = ("ivf_flat_quantize_ms", "ivf_flat_gather_ms",
           "ivf_flat_scan_ms")
SEARCH = {"k": 10, "n_probes": 4}


@pytest.fixture
def no_cache():
    """The persistent cache's key leaves metadata out: off, so that no
    entry compiled without the scopes is handed back."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        compilation_cache.reset_cache()
        jax.clear_caches()


@pytest.fixture(scope="module")
def index():
    x = np.random.default_rng(2).random((2000, 16), dtype=np.float32)
    return ivf_flat.build(jnp.asarray(x), ivf_flat.IndexParams(
        n_lists=32, list_size_cap_factor=None, seed=0)), x


def _ctx(index, route, calls=3):
    """A traced window of ``calls`` calls of the program the route runs:
    one op per instruction that does work, back to back, each 1 us
    longer than the last, in one program run per call."""
    idx, x = index
    batch, tile, program = ROUTES[route]
    queries = jnp.asarray(x[:batch])
    fn, args, kwargs = ivf_flat._plan(
        idx, queries, SEARCH["k"],
        ivf_flat.SearchParams(**ctx_search(tile)))
    text = fn.lower(*args, **kwargs).compile().as_text()
    names = [(ins.name, ins.opcode)
             for ins in scopes.executed(scopes.parse(text))
             if not ins.trivial]
    ops, runs, t = [], [], 1.0
    for _ in range(calls):
        t0 = t
        for i, (name, opcode) in enumerate(names):
            dur = (i + 1) * 1e-6
            ops.append(trace.Op(name, opcode, program, t, t + dur))
            t += dur
        runs.append((t0, t, program))
    summary = trace.Summary(window=(0.5, t + 0.5), busy_s=t - 1.0,
                            n_devices=1, ops=ops, gaps=[], modules=runs)
    return types.SimpleNamespace(
        trace=summary, window=types.SimpleNamespace(batches=[0] * calls),
        session=types.SimpleNamespace(batches=[queries]), index=idx,
        config={"search": {**SEARCH, **ctx_search(tile)}}), text


def ctx_search(tile):
    """The configuration's search block, less ``k``."""
    return {"n_probes": SEARCH["n_probes"], "query_tile": tile}


def _read(ctx, split):
    bench = Bench(ROOT)
    return {name: bench.reader(f"{name}.{split}").read(ctx)
            for name in READERS}


@pytest.mark.parametrize("route", list(ROUTES))
def test_stages_and_other_sum_to_the_programs_time(index, no_cache, route):
    ctx, text = _ctx(index, route)
    ms = ivf_flat_stages.stage_ms(ctx)
    stages = scopes.stage_map(text, ivf_flat_stages.STAGES)
    want = {stages[ins.name] for ins in scopes.executed(scopes.parse(text))
            if not ins.trivial} - {scopes.OTHER}
    assert {s for s in ivf_flat_stages.STAGES.values() if ms[s] > 0} == want
    assert want >= {"quantize", "scan"}
    if route == "per_query_tiled":
        assert "gather" in want
    parts = sum(ms[s] for s in list(ivf_flat_stages.STAGES.values())
                + [scopes.OTHER])
    assert parts == pytest.approx(ms["program"])
    got = _read(ctx, "throughput" if route == "grouped" else "latency")
    assert got == pytest.approx({
        "ivf_flat_quantize_ms": ms["quantize"],
        "ivf_flat_gather_ms": ms["gather"], "ivf_flat_scan_ms": ms["scan"]})
    assert got["ivf_flat_scan_ms"] > got["ivf_flat_quantize_ms"] > 0


def test_readers_find_nothing_without_the_programs_runs():
    ctx = types.SimpleNamespace(
        trace=trace.Summary(window=(0.0, 1.0), busy_s=0.0, n_devices=0,
                            ops=[], gaps=[]),
        window=types.SimpleNamespace(batches=[0, 0]))
    assert set(_read(ctx, "latency").values()) == {None}


def test_an_executable_built_from_other_scopes_raises(index, no_cache,
                                                      monkeypatch):
    ctx, _ = _ctx(index, "per_query")
    jax.clear_caches()
    monkeypatch.setattr(ivf_flat, "annotate",
                        lambda name: jax.named_scope(name + "_old"))
    with pytest.raises(RuntimeError, match="compile cache"):
        _read(ctx, "latency")


def test_a_trace_of_another_program_raises(index, no_cache):
    """Ops the executable compiled for the window's first call does not
    hold: the plan is not the one that ran."""
    ctx, _ = _ctx(index, "per_query")
    ctx.trace.ops.append(trace.Op("fusion.9999", "fusion",
                                  "jit__search_impl", 1.0, 1.0 + 1e-6))
    with pytest.raises(RuntimeError, match="not the program that ran"):
        _read(ctx, "latency")
