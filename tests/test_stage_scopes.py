"""Stage scopes inside the search programs, and spans on the profiler's
clock.

The search programs name their stages with ``core.tracing.annotate``
(a ``jax.named_scope``): the exact search's distance, tile cut, merge
and per-call layout work, the IVF searches' probe selection, lookup
table and scan, and the re-rank. These tests compile each program on
the CPU and check, in its optimized HLO text, that every instruction
that does work runs under a stage (``benchmark/scopes.py`` is the map
the benchmark reads the device trace with), and that a scope changes
nothing but metadata. The persistent compilation cache is off around
the compiles: its key leaves the metadata out, so an entry compiled
without the scopes could be handed back.

They also check that an obs span opens a profiler annotation on the
profiler's own clock, and that the IVF builds count the rows their
capped lists drop.
"""

import contextlib
import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.core import tracing
from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq, refine
from raft_tpu.neighbors import ivf_common as ic
from raft_tpu.obs.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import scopes  # noqa: E402


@pytest.fixture
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _obs_clean():
    yield
    obs.disable()


def _uncovered(text, stages):
    """Executed instructions that do work and map to no stage."""
    found = scopes.stage_map(text, stages)
    return sorted(ins.name for ins in scopes.executed(scopes.parse(text))
                  if not ins.trivial and found[ins.name] == scopes.OTHER)


def _stages(text, stages):
    found = scopes.stage_map(text, stages)
    return {found[ins.name] for ins in scopes.executed(scopes.parse(text))
            if not ins.trivial}


# -- the exact search -----------------------------------------------------

#: (batch, rows, impl, tile budget): one block, the tiled scan with the
#: bin cut, the tiled scan with a per-tile select_k
KNN_PATHS = {
    "single_block": (16, 2048, "auto", None),
    "tiled_bins": (8, 40000, "auto", 1 << 10),
    "tiled_sort": (8, 40000, "sort", 1 << 10),
}
KNN_WANT = {
    "single_block": {"distance", "select_k"},
    "tiled_bins": {"distance", "tile_cut", "merge", "prepare"},
    "tiled_sort": {"distance", "select_k", "merge", "prepare"},
}


def _knn_text(monkeypatch, path, with_scopes=True):
    m, n, impl, budget = KNN_PATHS[path]
    if budget is not None:
        monkeypatch.setattr(brute_force, "_TILE_BUDGET_ELEMS", budget)
    if not with_scopes:
        monkeypatch.setattr(brute_force, "annotate",
                            lambda name: contextlib.nullcontext())
    x = np.random.default_rng(0).random((n, 16), dtype=np.float32)
    index = brute_force.build(jnp.asarray(x))
    jax.clear_caches()  # trace afresh under the patched module
    try:
        return brute_force.knn.__wrapped__.lower(
            index, jnp.asarray(x[:m]), 10, impl=impl).compile().as_text()
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("path", list(KNN_PATHS))
def test_knn_stage_scopes_cover_the_program(monkeypatch, no_cache, path):
    text = _knn_text(monkeypatch, path)
    assert _uncovered(text, scopes.KNN_STAGES) == []
    assert _stages(text, scopes.KNN_STAGES) == KNN_WANT[path]


def _program(text):
    """The computations of an HLO text, without metadata and without
    the source-location tables."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return [line for line in text.splitlines()
            if line.startswith(("HloModule", "%", "ENTRY", " ", "}"))]


@pytest.mark.parametrize("path", ["single_block", "tiled_bins"])
def test_knn_scopes_change_only_metadata(monkeypatch, no_cache, path):
    scoped = _knn_text(monkeypatch, path)
    plain = _knn_text(monkeypatch, path, with_scopes=False)
    assert "raft_tpu.brute_force.distance" in scoped
    assert "raft_tpu.brute_force." not in plain
    assert _program(scoped) == _program(plain)


# -- the IVF searches and the re-rank --------------------------------------

@pytest.fixture(scope="module")
def ivf_indexes():
    x = np.random.default_rng(1).random((3000, 16), dtype=np.float32)
    flat = ivf_flat.build(jnp.asarray(x), ivf_flat.IndexParams(n_lists=16))
    pq = ivf_pq.build(jnp.asarray(x), ivf_pq.IndexParams(
        n_lists=16, pq_dim=8, seed=0, cache_reconstruction="never"))
    return x, flat, pq


# scope name -> stage of the IVF programs and of the re-rank, from the
# programs' own names (the benchmark reads no IVF cell yet)
IVF_FLAT_STAGES = {ivf_flat._COARSE: "coarse_quantize",
                   ivf_flat._SCAN: "scan"}
IVF_PQ_STAGES = {ivf_pq._COARSE: "coarse_quantize", ivf_pq._LUT: "lut",
                 ivf_pq._SCAN: "scan"}
REFINE_STAGES = {refine._REFINE: "refine"}

IVF_PROGRAMS = {
    "ivf_flat.per_query": ("flat", "per_query", IVF_FLAT_STAGES,
                           {"coarse_quantize", "scan"}),
    "ivf_flat.grouped": ("flat", "grouped", IVF_FLAT_STAGES,
                         {"coarse_quantize", "scan"}),
    "ivf_pq.per_query": ("pq", "per_query", IVF_PQ_STAGES,
                         {"coarse_quantize", "lut", "scan"}),
    "ivf_pq.grouped": ("pq", "grouped", IVF_PQ_STAGES,
                       {"coarse_quantize", "lut", "scan"}),
}


@pytest.mark.parametrize("program", list(IVF_PROGRAMS))
def test_ivf_stage_scopes_cover_the_program(ivf_indexes, no_cache,
                                            program):
    x, flat, pq = ivf_indexes
    kind, route, stages, want = IVF_PROGRAMS[program]
    index, mod = (flat, ivf_flat) if kind == "flat" else (pq, ivf_pq)
    q = jnp.asarray(x[:64])
    n_probes = 4
    if route == "per_query":
        lowered = mod._search_impl.lower(index, q, 10, n_probes, 16)
    else:
        seg = ic.SEGMENT_SIZE
        n_seg = ic.n_segments(q.shape[0] * n_probes, 16, seg)
        lowered = mod._search_grouped.lower(index, q, 10, n_probes, seg,
                                            n_seg, 4)
    text = lowered.compile().as_text()
    assert _uncovered(text, stages) == []
    assert _stages(text, stages) == want


def test_refine_program_runs_under_its_scope(ivf_indexes, no_cache):
    x, _, _ = ivf_indexes
    cand = jnp.asarray(np.arange(64 * 40).reshape(64, 40) % 3000,
                       jnp.int32)
    text = refine._refine_impl.lower(jnp.asarray(x), jnp.asarray(x[:64]),
                                     cand, 10, "sqeuclidean"
                                     ).compile().as_text()
    assert _uncovered(text, REFINE_STAGES) == []
    assert _stages(text, REFINE_STAGES) == {"refine"}


# -- spans on the profiler's timeline ---------------------------------------

def _host_events(log_dir):
    """``{name: [(start_ns on the realtime clock, duration_ns)]}`` of the
    host plane of the trace under ``log_dir``."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    data = ProfileData.from_file(path)
    start = next(dict(p.stats)["profile_start_time"] for p in data.planes
                 if p.name == "Task Environment")
    out = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (start + e.start_ns, e.duration_ns))
    return out


def test_span_lands_on_the_profiler_clock(tmp_path):
    from raft_tpu.obs import trace

    buf = trace.EventBuffer()
    prev = trace.set_buffer(buf)
    obs.enable(registry=MetricsRegistry(), hbm=False, events=True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracing.span("probe"):
                with tracing.span("inner"):
                    jnp.arange(8).block_until_ready()
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.disable()
        trace.set_buffer(prev)
    host = _host_events(str(tmp_path))
    assert len(host["raft_tpu.probe"]) == 1
    assert len(host["raft_tpu.probe.inner"]) == 1
    ring = {e["name"]: e for e in buf.snapshot() if e["ph"] == "X"}
    for name in ("probe", "probe.inner"):
        start_ns, dur_ns = host["raft_tpu." + name][0]
        assert abs(start_ns * 1e-9 - ring[name]["ts"]) < 50e-6, name
        assert abs(dur_ns * 1e-9 - ring[name]["dur"]) < 1e-3, name


def test_span_annotates_with_obs_off(tmp_path):
    reg = obs.get_registry()
    assert not obs.enabled()
    assert not obs.profiling()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.profiling()
        with tracing.span("serve") as sp:
            with tracing.span("dispatch"):
                pass
        assert not sp._open
    finally:
        jax.profiler.stop_trace()
    host = _host_events(str(tmp_path))
    assert "raft_tpu.serve" in host and "raft_tpu.serve.dispatch" in host
    assert obs.current_name() == ""
    assert "span.serve" not in reg.snapshot()["histograms"]


def test_traced_entry_annotates_once(tmp_path):
    @tracing.traced("raft_tpu.test.entry")
    def entry(x):
        with tracing.span("stage"):
            return x + 1

    obs.enable(registry=MetricsRegistry(), hbm=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert entry(1) == 2
    finally:
        jax.profiler.stop_trace()
    host = _host_events(str(tmp_path))
    assert len(host["raft_tpu.test.entry"]) == 1
    assert len(host["raft_tpu.test.entry.stage"]) == 1


# -- rows the capped lists drop ----------------------------------------------

@pytest.mark.parametrize("kind", ["ivf_flat", "ivf_pq"])
def test_build_counts_rows_dropped(kind):
    """Skewed data into lists capped at the mean: the counter holds
    every row the index lost."""
    rng = np.random.default_rng(3)
    x = np.concatenate([rng.normal(0, 0.01, (1800, 8)),
                        rng.normal(5, 1.0, (200, 8))]).astype(np.float32)
    reg = MetricsRegistry()
    obs.enable(registry=reg, hbm=False)
    if kind == "ivf_flat":
        index = ivf_flat.build(jnp.asarray(x), ivf_flat.IndexParams(
            n_lists=8, list_size_cap_factor=1.0, spill=True))
    else:
        index = ivf_pq.build(jnp.asarray(x), ivf_pq.IndexParams(
            n_lists=8, pq_dim=4, seed=0, list_size_cap_factor=1.0,
            spill=True, cache_reconstruction="never"))
    obs.disable()
    lost = x.shape[0] - int(np.asarray(index.list_sizes).sum())
    counted = reg.snapshot()["counters"].get(
        "ivf.build.rows_dropped{index=%s}" % kind, 0)
    assert lost > 0
    assert counted == lost
