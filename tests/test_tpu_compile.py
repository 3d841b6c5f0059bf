"""Main-path Pallas kernels compiled for a TPU v5e at hard-1M widths.

Interpret mode (every other kernel test) checks results but not what
Mosaic accepts: block shapes that do not tile, layouts it cannot
relayout, scoped VMEM overruns and removed compiler-parameter classes
all pass there and fail only on the chip. The TPU compiler is
installed with JAX, and it compiles for a chip that is described and
not attached, so these tests lower and compile each kernel for device
0 of a described ``v5e:2x2`` (the ring merge for all four) and check
that the kernel is in the compiled program. Nothing runs. The exact
search's program at the benchmark's shapes is compiled here too, to
check that the chip's compiler keeps every instruction that does work
under a stage scope, the layout copies it inserts included.

The topology is described inside a module-scoped fixture, never while
a module is imported: only the one test worker given this file loads
the TPU library. The persistent compilation cache is off around the
compiles — an entry written for a described chip cannot be read back
without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from raft_tpu.ops import pallas_kernels as pk

# hard-1M×128 at the benchmark's build params: 1024 lists, spill with
# list_size_cap_factor 1.5 → 1472-row lists; batch 10,000 at
# n_probes 64 → 10,000·64/128 + 1024 segments of 128 query slots
N, DIM, N_LISTS, L, SEG = 1_000_000, 128, 1024, 1472, 128
BATCH, N_PROBES, K = 10_000, 64, 10
N_SEG = BATCH * N_PROBES // SEG + N_LISTS
PQ_DIM, PQ_BITS = 64, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        compilation_cache.reset_cache()
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_segmented_scan_topk(one_chip, dtype):
    """IVF-Flat's scan (f32 lists) and IVF-PQ's recon-cache scan (bf16)."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    text = _compiled_text(
        lambda sl, qv, packed, ids: pk.segmented_scan_topk(
            sl, qv, packed, ids, "l2"),
        s((N_SEG,), jnp.int32), s((N_SEG, SEG, DIM), jnp.float32),
        s((N_LISTS, L, DIM), jnp.dtype(dtype)),
        s((N_LISTS, L), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lut_dtype", ["float32", "float8_e4m3"])
def test_ivfpq_lut_scan_topk(one_chip, lut_dtype):
    """The fused IVF-PQ LUT scan over packed 8-bit codes: exact f32 and
    the fp8 codebook TPU dispatch picks for n_probes ≥ 64."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    text = _compiled_text(
        lambda *a: pk.ivfpq_lut_scan_topk(
            *a, "l2", pq_bits=PQ_BITS, pq_dim=PQ_DIM, L=L,
            lut_dtype=lut_dtype),
        s((N_SEG,), jnp.int32), s((N_SEG, SEG, DIM), jnp.float32),
        s((N_LISTS, L, PQ_DIM * PQ_BITS // 8), jnp.uint8),
        s((N_LISTS, L), jnp.int32), s((N_LISTS, L), jnp.float32),
        s((N_LISTS, DIM), jnp.float32),
        s((PQ_DIM, 1 << PQ_BITS, DIM // PQ_DIM), jnp.float32))
    assert "tpu_custom_call" in text


def test_gather_refine_topk(one_chip):
    """Exact re-rank of 40 candidates per query over the 1M×128 base."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    text = _compiled_text(
        lambda base, q, cand: pk.gather_refine_topk(base, q, cand, K),
        s((N, DIM), jnp.float32), s((BATCH, DIM), jnp.float32),
        s((BATCH, 4 * K), jnp.int32))
    assert "tpu_custom_call" in text


def test_select_k_pallas(one_chip):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    text = _compiled_text(lambda x: pk.select_k_pallas(x, K),
                          s((BATCH, 16_384), jnp.float32))
    assert "tpu_custom_call" in text


def test_fused_l2_argmin(one_chip):
    """k-means assignment of a build chunk to the 1024 coarse centers."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    text = _compiled_text(pk.fused_l2_argmin,
                          s((1 << 16, DIM), jnp.float32),
                          s((N_LISTS, DIM), jnp.float32))
    assert "tpu_custom_call" in text


def test_ring_topk_merge_four_chips(topo):
    """The ring merge's remote DMAs and semaphores on a 4-chip mesh at
    the largest query batch its VMEM gate admits there."""
    from jax import shard_map

    n_dev, m = 4, 4096
    assert pk.ring_topk_kernel_ok(m, K, n_dev)
    mesh = Mesh(list(topo.devices[:n_dev]), ("shard",))
    rows = NamedSharding(mesh, P("shard", None))

    def body(v, i):
        return pk.ring_topk_merge(v, i, K, "shard", n_dev)

    merge = shard_map(body, mesh=mesh,
                      in_specs=(P("shard", None), P("shard", None)),
                      out_specs=(P("shard", None), P("shard", None)),
                      check_vma=False)
    text = _compiled_text(merge,
                          _spec(rows, (n_dev * m, K), jnp.float32),
                          _spec(rows, (n_dev * m, K), jnp.int32))
    assert "tpu_custom_call" in text


def test_sharded_build_labels_its_trainset_four_chips(topo, monkeypatch):
    """The sharded IVF-PQ build labels its mesh-replicated PQ trainset
    with the Pallas argmin, which XLA cannot partition across a mesh by
    itself: the call must sit inside ``shard_map``."""
    from raft_tpu.parallel import ivf as pivf

    monkeypatch.setattr(pk, "_on_tpu", lambda: True)  # dispatch as on a chip
    mesh = Mesh(list(topo.devices[:4]), ("shard",))
    rep = NamedSharding(mesh, P())
    text = _compiled_text(
        lambda t, c: pivf._label_replicated(t, c, mesh),
        _spec(rep, (1 << 16, DIM), jnp.float32),
        _spec(rep, (N_LISTS, DIM), jnp.float32))
    assert "tpu_custom_call" in text


# -- stage scopes at the exact search's cells, on the chip's compiler ------

def _scopes():
    """The benchmark's stage map (``benchmark/scopes.py``)."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import scopes

    return scopes


def _working_stages(text, stages):
    """``{instruction: stage}`` of the executed instructions that do
    work."""
    scopes = _scopes()
    found = scopes.stage_map(text, stages)
    return {ins.name: found[ins.name]
            for ins in scopes.executed(scopes.parse(text))
            if not ins.trivial}


@pytest.mark.parametrize("batch", [BATCH, 10])
def test_knn_stages_cover_the_chips_program(one_chip, monkeypatch, batch):
    """``brute_force.knn`` over 1M×128 as the benchmark's cells call it:
    every instruction that does work — the layout copies the chip's
    compiler inserts too — maps to a stage, and at batch 10 the Pallas
    select's call keeps the select's scope."""
    from raft_tpu.neighbors import brute_force

    scopes = _scopes()
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)  # dispatch as on a chip
    jax.clear_caches()  # no CPU-dispatch trace of the same shapes
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    index = brute_force.BruteForceIndex(dataset=s((N, DIM), jnp.float32),
                                        norms=s((N,), jnp.float32))
    text = brute_force.knn.__wrapped__.lower(
        index, s((batch, DIM), jnp.float32), K).compile().as_text()
    jax.clear_caches()
    stages = _working_stages(text, scopes.KNN_STAGES)
    assert scopes.OTHER not in stages.values(), sorted(
        n for n, st in stages.items() if st == scopes.OTHER)
    if batch == BATCH:
        assert set(stages.values()) == {"distance", "tile_cut", "merge",
                                        "prepare"}
        assert any(n.startswith("copy") and st == "tile_cut"
                   for n, st in stages.items())
    else:
        assert stages["select_k_pallas.1"] == "select_k"
        assert set(stages.values()) == {"distance", "select_k"}


def test_ivf_scan_kernel_takes_the_scan_scope(one_chip, monkeypatch):
    """IVF-Flat's grouped search through the segmented scan kernel: the
    kernel's call runs under the scan's scope (a small index; the
    scope, not the size, is what is checked)."""
    from raft_tpu.neighbors import ivf_common as ic
    from raft_tpu.neighbors import ivf_flat

    scopes = _scopes()
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    jax.clear_caches()
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    n_lists, lsize, batch, n_probes = 64, 256, 256, 8
    index = ivf_flat.IvfFlatIndex(
        centers=s((n_lists, DIM), jnp.float32),
        packed_data=s((n_lists, lsize, DIM), jnp.float32),
        packed_ids=s((n_lists, lsize), jnp.int32),
        packed_norms=s((n_lists, lsize), jnp.float32),
        list_sizes=s((n_lists,), jnp.int32), metric="sqeuclidean")
    n_seg = ic.n_segments(batch * n_probes, n_lists, SEG)
    text = ivf_flat._search_grouped.lower(
        index, s((batch, DIM), jnp.float32), K, n_probes, SEG, n_seg, 4,
        use_segk=True, select_impl="approx").compile().as_text()
    jax.clear_caches()
    stages = _working_stages(text, {ivf_flat._COARSE: "coarse_quantize",
                                    ivf_flat._SCAN: "scan"})
    assert stages["segmented_scan_topk.1"] == "scan"
    assert set(stages.values()) == {"coarse_quantize", "scan"}
