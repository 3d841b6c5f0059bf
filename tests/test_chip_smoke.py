"""CPU rehearsal of chip_smoke.py: its phases at 20,000 × 128 on the
CPU mesh, dispatching as on a TPU.

The test steers the program itself: ``pallas_kernels._on_tpu`` reads
True, so every dispatch picks what it picks on a chip, and every
kernel runs in Pallas's TPU interpret mode (simulated VMEM, DMAs and
semaphores). The sharded phase keeps XLA's CPU paths for the per-shard
work and steers only the merge tier onto the ring kernel, run in
interpret mode — the TPU interpret mode is far too slow for a sharded
build. Widths are the real ones (128 dims, pq_dim 64); depth, lists and
batches are cut.
"""

import importlib.util
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(n=20_000, dim=128, n_queries=64, n_ref=8, batch=8,
             n_lists=64, n_probes=32, n_served=8, sharded_batch=64)


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def size(cs):
    return cs.Size(**SMALL)


@pytest.fixture(scope="module")
def data(cs, size):
    base, queries = cs.make_data(size, seed=0)
    ref = cs.exact_topk(base, queries[:size.n_ref], size.k)
    return jnp.asarray(base), jnp.asarray(queries), ref


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Dispatch as on a TPU; run every kernel in TPU interpret mode. The
    mode is process-global (the server's batcher thread must see it),
    and programs traced under it are dropped afterwards."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    pltpu.set_tpu_interpret_mode()
    try:
        yield
    finally:
        pltpu.set_tpu_interpret_mode(None)
        jax.clear_caches()


def test_exact_reference_matches_a_full_sort(cs):
    rng = np.random.default_rng(3)
    base = rng.standard_normal((3000, 16)).astype(np.float32)
    queries = rng.standard_normal((20, 16)).astype(np.float32)
    d = ((queries[:, None, :].astype(np.float64) - base[None]) ** 2).sum(-1)
    want = np.argsort(d, axis=1, kind="stable")[:, :10]
    np.testing.assert_array_equal(
        cs.exact_topk(base, queries, 10, block=512), want)
    assert cs.recall(want, want) == 1.0
    assert cs.recall(np.full_like(want, -1), want) == 0.0


def test_phase_brute_force(cs, size, data, tpu_dispatch):
    row = cs.phase_brute_force(*data, size)
    assert row["recall"] == 1.0
    assert row["counters"].get("select_k.dispatch{impl=pallas}", 0) > 0


def test_brute_force_refuses_a_select_k_fallback(cs, size, data):
    # off-TPU dispatch gives the 20,000-wide rows to XLA's top-k: the
    # phase must say a kernel gave way instead of passing quietly
    with pytest.raises(cs.SmokeFailure, match="select_k"):
        cs.phase_brute_force(*data, size)


def test_phase_ivf_flat(cs, size, data, tpu_dispatch):
    row = cs.phase_ivf_flat(*data, size)
    assert row["recall"] >= 0.90


def test_phase_ivf_pq_then_served(cs, size, data, tpu_dispatch):
    base, queries, ref = data
    index, rows = cs.phase_ivf_pq(base, queries, ref, size)
    assert cs._dispatched(rows[0]["counters"], "ivf_pq.scan") == {"segk"}
    assert cs._dispatched(rows[1]["counters"], "refine") == {
        "pallas_gather"}
    row = cs.phase_served(index, base, queries, size)
    assert row["mismatched_rows"] == 0 and row["requests"] == size.n_served


def test_phase_sharded(cs, size, data, monkeypatch):
    from raft_tpu.parallel import merge

    # the merge tier sees a TPU and runs the ring kernel interpreted;
    # everything else keeps its CPU path
    shim = types.SimpleNamespace(**vars(pk))
    shim._on_tpu = lambda: True
    shim.ring_topk_merge = lambda *a, **kw: pk.ring_topk_merge(
        *a, **{**kw, "interpret": True})
    monkeypatch.setattr(merge, "_pk", shim)
    try:
        rows = cs.phase_sharded(*data, size, devices=jax.devices()[:4])
    finally:
        jax.clear_caches()
    assert len(rows) == 4
    assert rows[0]["counters"].get(
        "parallel.merge.dispatch{impl=ring_kernel}", 0) > 0


def test_run_phase_rejects_a_wrong_answer(cs, data):
    _, _, ref = data
    with pytest.raises(cs.SmokeFailure, match="recall"):
        cs.run_phase("wrong", lambda: jnp.zeros(1),
                     lambda index: (jnp.zeros(ref.shape),
                                    jnp.zeros(ref.shape, jnp.int32)),
                     ref, 0.5)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_the_cpu(cs, argv):
    with pytest.raises(SystemExit) as exc:
        cs.main(argv)
    assert "platform 'cpu'" in str(exc.value.code)


_CACHE_PROBE = """
import importlib.util, sys
sys.path.insert(0, {root!r})
spec = importlib.util.spec_from_file_location("script", {path!r})
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
try:
    mod.main()
except SystemExit as e:
    print("EXIT", e.code)
import jax
print("CACHE", jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
@pytest.mark.parametrize("env_dir", [None, "/x"])
def test_compile_cache_placed_from_outside(script, env_dir):
    """Both scripts leave JAX_COMPILATION_CACHE_DIR's directory in place
    and otherwise use the repo's fixed .cache/jax — and both refuse the
    CPU with a non-zero exit naming it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = _CACHE_PROBE.format(root=ROOT,
                               path=os.path.join(ROOT, script))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    lines = p.stdout.splitlines()
    assert any(ln.startswith("EXIT ") and "'cpu'" in ln
               for ln in lines), p.stdout
    want = env_dir or os.path.join(ROOT, ".cache", "jax")
    assert f"CACHE {want}" in lines, p.stdout


def test_main_path_matmuls_pin_precision(cs, monkeypatch):
    """Every matmul the smoke's builds and searches trace names its
    precision. Left at the default it is one bf16 pass on a TPU — exact
    f32 here, so no CPU result can show it — and bf16 expanded-form L2
    ranks clustered data wrongly (IVF-Flat recall@10 0.27 on the chip)."""
    import traceback

    from jax._src.lax import lax as lax_internal
    from raft_tpu.neighbors import ivf_flat, ivf_pq

    offenders = set()
    real = lax_internal.dot_general

    def recording(lhs, rhs, dimension_numbers, precision=None, **kw):
        ps = precision if isinstance(precision, tuple) else (precision,)
        if all(p in (None, jax.lax.Precision.DEFAULT) for p in ps):
            for fr in reversed(traceback.extract_stack()[:-1]):
                if os.sep + "raft_tpu" + os.sep in fr.filename:
                    offenders.add(f"{fr.filename}:{fr.lineno}")
                    break
        return real(lhs, rhs, dimension_numbers, precision=precision, **kw)

    # jnp's matmuls call the internal function; package code calling
    # jax.lax.dot_general holds the public binding
    monkeypatch.setattr(lax_internal, "dot_general", recording)
    monkeypatch.setattr(jax.lax, "dot_general", recording)
    jax.clear_caches()  # retrace every jitted program through the recorder
    try:
        size = cs.Size(n=6000, dim=32, n_queries=32, batch=32, n_lists=32,
                       n_probes=8, pq_dim=16)
        base, queries = cs.make_data(size, seed=0)
        base, queries = jnp.asarray(base), jnp.asarray(queries)
        flat = ivf_flat.build(base, ivf_flat.IndexParams(
            n_lists=size.n_lists, spill=True, list_size_cap_factor=1.5))
        ivf_flat.search(flat, queries, 10, ivf_flat.SearchParams(
            n_probes=size.n_probes, scan_select="approx"))
        pq = ivf_pq.build(base, ivf_pq.IndexParams(
            n_lists=size.n_lists, pq_dim=size.pq_dim, spill=True,
            list_size_cap_factor=1.5))
        ivf_pq.search(pq, queries, 10, cs.pq_search_params(size, 4.0),
                      dataset=base)
    finally:
        jax.clear_caches()
    assert not offenders, sorted(offenders)
