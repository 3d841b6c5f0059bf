"""Tests of the stage map (``benchmark/scopes.py``) and the exact
search's per-stage readers (``metrics/knn_*_ms.py``) on the CPU.

The readers run on a synthetic trace summary whose ops carry the real
instruction names of the program compiled here, so the map they build
from the compiled program is the one that names the ops.
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

from benchmark import scopes, trace  # noqa: E402
from benchmark.manifest import Bench  # noqa: E402
from raft_tpu.neighbors import brute_force  # noqa: E402

READERS = {"distance": "knn_distance_ms", "select": "knn_select_ms",
           "other": "knn_other_ms"}


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


_SCOPE_NAMES = ("_DISTANCE", "_TILE_CUT", "_MERGE", "_PREPARE")


def _ctx(monkeypatch, tiled, scoped=True, calls=3):
    """A traced window of ``calls`` calls of the CPU-compiled program:
    one op per instruction that does work, back to back, each 1 us
    longer than the last, in one program run per call. ``scoped=False``
    is a program that neither declares nor opens stage scopes."""
    if tiled:
        monkeypatch.setattr(brute_force, "_TILE_BUDGET_ELEMS", 1 << 10)
    if not scoped:
        monkeypatch.setattr(brute_force, "annotate",
                            lambda name: contextlib.nullcontext())
        for attr in _SCOPE_NAMES:
            monkeypatch.setattr(brute_force, attr, None)
    m, n = (8, 40000) if tiled else (16, 2048)
    x = np.random.default_rng(0).random((n, 16), dtype=np.float32)
    index = brute_force.build(jnp.asarray(x))
    queries = jnp.asarray(x[:m])
    text = brute_force.knn.__wrapped__.lower(index, queries, 10
                                             ).compile().as_text()
    names = [(ins.name, ins.opcode)
             for ins in scopes.executed(scopes.parse(text))
             if not ins.trivial]
    ops, runs, t = [], [], 1.0
    for _ in range(calls):
        t0 = t
        for i, (name, opcode) in enumerate(names):
            dur = (i + 1) * 1e-6
            ops.append(trace.Op(name, opcode, scopes.PROGRAM, t, t + dur))
            t += dur
        runs.append((t0, t, scopes.PROGRAM))
    ops.append(trace.Op("while.6", "while", scopes.PROGRAM, 1.0, t))
    summary = trace.Summary(window=(0.5, t + 0.5), busy_s=t - 1.0,
                            n_devices=1, ops=ops, gaps=[], modules=runs)
    ctx = types.SimpleNamespace(
        trace=summary, window=types.SimpleNamespace(batches=[0] * calls),
        session=types.SimpleNamespace(batches=[queries]), index=index,
        config={"search": {"k": 10}})
    return ctx, text


def _read(ctx):
    bench = Bench(ROOT)
    return {group: bench.reader(name + ".throughput").read(ctx)
            for group, name in READERS.items()}


@pytest.mark.parametrize("tiled", [False, True],
                         ids=["single_block", "tiled"])
def test_readers_on_the_compiled_programs_names(monkeypatch, no_cache,
                                                tiled):
    ctx, text = _ctx(monkeypatch, tiled)
    got = _read(ctx)
    stages = scopes.stage_map(text, scopes.KNN_STAGES)
    want = {group: 0.0 for group in READERS}
    for op in ctx.trace.ops:
        if op.opcode == "while":
            continue
        stage = stages[op.name]
        group = next((g for g, members in scopes.GROUPS.items()
                      if stage in members), "other")
        want[group] += 1e3 * (op.end - op.start) / 3
    assert got == pytest.approx(want)
    assert got["distance"] > 0 and got["select"] > 0
    if tiled:
        assert got["other"] > 0  # the base padded and cut into tiles


@pytest.mark.parametrize("tiled", [False, True],
                         ids=["single_block", "tiled"])
def test_stages_sum_to_the_programs_time(monkeypatch, no_cache, tiled):
    ctx, _ = _ctx(monkeypatch, tiled)
    got = _read(ctx)
    per_call = 1e3 * ctx.trace.module_seconds([scopes.PROGRAM]) / 3
    assert sum(got.values()) == pytest.approx(per_call)


def test_readers_find_nothing_in_a_program_without_scopes(monkeypatch,
                                                         no_cache):
    ctx, text = _ctx(monkeypatch, tiled=True, scoped=False)
    assert scopes.scopes_in(text, "raft_tpu.brute_force.") == set()
    assert _read(ctx) == {group: None for group in READERS}


STALE = {
    # code that opens no scopes filled the cache
    "no_scopes": lambda name: contextlib.nullcontext(),
    # code whose scopes had other names filled the cache
    "other_scopes": lambda name: jax.named_scope(name + "_old"),
}


@pytest.mark.parametrize("stale", list(STALE))
def test_an_executable_built_from_other_scopes_raises(monkeypatch,
                                                      no_cache, stale):
    """The persistent cache's key leaves metadata out, so it can hand
    back an executable compiled from code with other scopes: the
    readers refuse its split rather than report a wrong one."""
    ctx, _ = _ctx(monkeypatch, tiled=True)
    jax.clear_caches()
    monkeypatch.setattr(brute_force, "annotate", STALE[stale])
    with pytest.raises(RuntimeError, match="compile cache"):
        _read(ctx)


def test_readers_find_nothing_without_the_programs_runs():
    ctx = types.SimpleNamespace(
        trace=trace.Summary(window=(0.0, 1.0), busy_s=0.0, n_devices=0,
                            ops=[], gaps=[]),
        window=types.SimpleNamespace(batches=[0, 0]))
    assert _read(ctx) == {group: None for group in READERS}


# -- the map on a small program text ---------------------------------------

_TEXT = """HloModule jit_knn, entry_computation_layout={(f32[8,16]{1,0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8,16]) -> f32[8,128] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  ROOT %dot.1 = f32[8,128]{1,0} dot(%param_0.1, %param_0.1), metadata={op_name="jit(knn)/raft_tpu.brute_force.distance/dot_general"}
}

%fused_computation.2 (param_0.2: f32[8,128]) -> f32[8] {
  %param_0.2 = f32[8,128]{1,0} parameter(0)
  ROOT %reduce.2 = f32[8]{0} reduce(%param_0.2), metadata={op_name="jit(knn)/raft_tpu.brute_force.tile_cut/reduce_min"}
}

ENTRY %main.3 (p.1: f32[8,16]) -> (f32[8], f32[8,128]) {
  %p.1 = f32[8,16]{1,0} parameter(0), metadata={op_name="queries"}
  %fusion.15 = f32[8,128]{1,0:T(8,128)} fusion(f32[8,16]{1,0:T(8,128)} %p.1), kind=kOutput, calls=%fused_computation.1
  %copy.40 = f32[8,128]{0,1:T(8,128)} copy(f32[8,128]{1,0:T(8,128)} %fusion.15)
  %fusion.16 = f32[8]{0} fusion(f32[8,128]{0,1:T(8,128)} %copy.40), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(knn)/raft_tpu.brute_force.tile_cut/reduce_min"}
  %copy.41 = f32[8,128]{1,0} copy(f32[8,128]{1,0:T(8,128)} %fusion.15)
  %custom-call.9 = f32[8]{0} custom-call(f32[8]{0} %fusion.16), custom_call_target="tpu_custom_call"
  ROOT %tuple.1 = (f32[8]{0}, f32[8,128]{1,0}) tuple(%custom-call.9, %copy.41)
}
"""


def test_an_unscoped_copy_takes_its_consumers_stage():
    stages = scopes.stage_map(_TEXT, scopes.KNN_STAGES)
    # the fusion's own metadata names nothing: its fused body names it
    assert stages["fusion.15"] == "distance"
    # a layout copy between two stages: its consumer's
    assert stages["copy.40"] == "tile_cut"
    # a copy whose only consumer does no work: its operand's
    assert stages["copy.41"] == "distance"
    # an unscoped kernel call: its operand's, its consumer doing no work
    assert stages["custom-call.9"] == "tile_cut"
    # fusion bodies do not run as ops of their own
    assert "dot.1" not in stages and "reduce.2" not in stages


def test_an_unknown_instruction_lands_in_other():
    stages = scopes.stage_map(_TEXT, scopes.KNN_STAGES)
    ops = [trace.Op("fusion.15", "fusion", scopes.PROGRAM, 0.0, 0.004),
           trace.Op("copy.40", "copy", scopes.PROGRAM, 0.004, 0.006),
           trace.Op("fusion.99", "fusion", scopes.PROGRAM, 0.006, 0.007),
           trace.Op("while.1", "while", scopes.PROGRAM, 0.0, 0.007)]
    got = scopes.group_ms(ops, stages, (0.0, 1.0), calls=1)
    assert got == pytest.approx({"distance": 4.0, "select": 2.0,
                                 "other": 1.0})
    # an op that spills past the window counts only inside it
    assert scopes.group_ms(ops, stages, (0.0, 0.005), calls=1) == \
        pytest.approx({"distance": 4.0, "select": 1.0, "other": 0.0})


def test_stage_of_takes_the_innermost_known_scope():
    assert scopes.stage_of(
        "jit(knn)/while/body/closed_call/raft_tpu.brute_force.tile_cut/"
        "raft_tpu.select_k/top_k", scopes.KNN_STAGES) == "select_k"
    assert scopes.stage_of(
        "jit(_search_grouped)/raft_tpu.ivf_pq.scan/raft_tpu.select_k/"
        "top_k", {"raft_tpu.ivf_pq.scan": "scan"}) == "scan"
    assert scopes.stage_of("jit(knn)/while/body/add",
                           scopes.KNN_STAGES) is None
