"""Brute-force kNN tests vs naive reference (reference test model:
cpp/internal/raft_internal/neighbors/naive_knn.cuh:82 + recall thresholds
in cpp/test/neighbors/ann_utils.cuh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.spatial.distance import cdist

from raft_tpu.neighbors import brute_force


def naive_knn(x, y, k, metric="sqeuclidean", select_min=True):
    d = cdist(x, y, metric) if metric != "ip" else -(x @ y.T)
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=1), idx


def recall(got_idx, ref_idx):
    hits = sum(len(set(g) & set(r)) for g, r in zip(got_idx, ref_idx))
    return hits / ref_idx.size


@pytest.mark.parametrize("metric,scipy_metric", [
    ("sqeuclidean", "sqeuclidean"),
    ("euclidean", "euclidean"),
    ("cosine", "cosine"),
])
def test_knn_exact(rng, metric, scipy_metric):
    x = rng.random((500, 32), dtype=np.float32)
    q = rng.random((40, 32), dtype=np.float32)
    idx = brute_force.build(jnp.asarray(x), metric=metric)
    dists, ids = brute_force.knn(idx, jnp.asarray(q), k=10)
    ref_d, ref_i = naive_knn(q, x, 10, scipy_metric)
    assert recall(np.asarray(ids), ref_i) >= 0.99
    np.testing.assert_allclose(np.sort(np.asarray(dists), 1),
                               np.sort(ref_d, 1), rtol=1e-3, atol=1e-4)


def test_knn_inner_product(rng):
    x = rng.random((300, 16), dtype=np.float32)
    q = rng.random((20, 16), dtype=np.float32)
    dists, ids = brute_force.knn_arrays(jnp.asarray(x), jnp.asarray(q), 5,
                                        metric="inner_product")
    sims = q @ x.T
    ref_i = np.argsort(-sims, axis=1)[:, :5]
    assert recall(np.asarray(ids), ref_i) >= 0.99


def test_knn_tiled_matches_untiled(rng, monkeypatch):
    """Force the scan-tiled path and check it agrees with one-shot."""
    from raft_tpu.neighbors import brute_force as bf

    x = rng.random((1000, 24), dtype=np.float32)
    q = rng.random((30, 24), dtype=np.float32)
    d1, i1 = bf.knn_arrays(jnp.asarray(x), jnp.asarray(q), 10)
    monkeypatch.setattr(bf, "_TILE_BUDGET_ELEMS", 30 * 128)
    d2, i2 = bf.knn_arrays(jnp.asarray(x), jnp.asarray(q), 10)
    np.testing.assert_allclose(np.sort(np.asarray(d1), 1),
                               np.sort(np.asarray(d2), 1), rtol=1e-5)
    assert recall(np.asarray(i2), np.asarray(i1)) >= 0.999


def test_knn_general_metric(rng):
    x = rng.random((200, 8), dtype=np.float32)
    q = rng.random((10, 8), dtype=np.float32)
    dists, ids = brute_force.knn_arrays(jnp.asarray(x), jnp.asarray(q), 5,
                                        metric="cityblock")
    ref_d, ref_i = naive_knn(q, x, 5, "cityblock")
    assert recall(np.asarray(ids), ref_i) >= 0.99


def test_validation(rng):
    x = jnp.zeros((10, 4))
    idx = brute_force.build(x)
    from raft_tpu.core import LogicError
    with pytest.raises(LogicError):
        brute_force.knn(idx, jnp.zeros((3, 5)), 2)  # dim mismatch
    with pytest.raises(LogicError):
        brute_force.knn(idx, jnp.zeros((3, 4)), 11)  # k > n


def test_tiled_bins_path_matches_exact(rng, monkeypatch):
    """Force the multi-tile scan (strided-bin cut) and compare with the
    guaranteed-exact per-tile selection and numpy."""
    from raft_tpu.neighbors import brute_force as bf

    monkeypatch.setattr(bf, "_TILE_BUDGET_ELEMS", 1 << 16)
    x = rng.random((3000, 24), dtype=np.float32)
    q = rng.random((40, 24), dtype=np.float32)
    idx = bf.build(jnp.asarray(x), metric="sqeuclidean")
    v1, i1 = bf.knn(idx, jnp.asarray(q), 10)
    v2, i2 = bf.knn(idx, jnp.asarray(q), 10, impl="sort")
    d = ((q[:, None, :] - x[None]) ** 2).sum(-1)
    gt = np.sort(d, axis=1)[:, :10]
    np.testing.assert_allclose(np.asarray(v1), gt, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(v2), gt, rtol=1e-4, atol=1e-4)
    # ip metric through the bins path too
    idx_ip = bf.build(jnp.asarray(x), metric="inner_product")
    vip, iip = bf.knn(idx_ip, jnp.asarray(q), 10)
    sip = q @ x.T
    np.testing.assert_allclose(np.asarray(vip),
                               -np.sort(-sip, axis=1)[:, :10],
                               rtol=1e-4, atol=1e-4)


def _tiled_inputs(rng, metric):
    """Two 16,384-row tiles (the tile's floor) at a small query batch,
    the distance budget cut so that the scan tiles."""
    from raft_tpu.neighbors import brute_force as bf

    x = rng.random((20_000, 16), dtype=np.float32)
    q = rng.random((24, 16), dtype=np.float32)
    assert bf._choose_tiles(24, 20_000, 16)[1] < 20_000
    return bf.build(jnp.asarray(x), metric=metric), jnp.asarray(q)


@pytest.fixture
def tile_cut_as_on_tpu(monkeypatch):
    """The tile cut dispatches as on a TPU, its kernel in TPU interpret
    mode; programs traced under it are dropped afterwards."""
    from jax.experimental.pallas import tpu as pltpu
    from raft_tpu.ops import pallas_kernels as pk

    def on():
        jax.clear_caches()
        monkeypatch.setattr(pk, "_on_tpu", lambda: True)
        pltpu.set_tpu_interpret_mode()

    try:
        yield on
    finally:
        pltpu.set_tpu_interpret_mode(None)
        jax.clear_caches()


@pytest.mark.parametrize("metric", ["sqeuclidean", "inner_product"])
def test_tiled_knn_same_with_the_kernel(rng, monkeypatch, tile_cut_as_on_tpu,
                                        metric):
    """The tiled scan returns the same ids and distances with the tile
    cut's Pallas kernel as with the XLA cut."""
    from raft_tpu.neighbors import brute_force as bf

    monkeypatch.setattr(bf, "_TILE_BUDGET_ELEMS", 1 << 16)
    idx, q = _tiled_inputs(rng, metric)
    jax.clear_caches()
    v_xla, i_xla = bf.knn(idx, q, 10)
    tile_cut_as_on_tpu()
    v_pk, i_pk = bf.knn(idx, q, 10)
    np.testing.assert_array_equal(np.asarray(i_pk), np.asarray(i_xla))
    np.testing.assert_array_equal(np.asarray(v_pk), np.asarray(v_xla))


def test_tile_cut_counts_its_engine(rng, monkeypatch, tile_cut_as_on_tpu):
    """Off the TPU a tiled trace counts the XLA cut and why; dispatched
    as on a TPU it counts the Pallas kernel."""
    from raft_tpu import obs
    from raft_tpu.neighbors import brute_force as bf

    monkeypatch.setattr(bf, "_TILE_BUDGET_ELEMS", 1 << 16)
    idx, q = _tiled_inputs(rng, "sqeuclidean")

    def counters():
        reg = obs.MetricsRegistry()
        obs.enable(registry=reg, hbm=False)
        try:
            bf.knn(idx, q, 10)[0].block_until_ready()
        finally:
            obs.disable()
        return reg.snapshot()["counters"]

    jax.clear_caches()
    c = counters()
    assert c.get("brute_force.tile_cut.fallback{reason=off_tpu}", 0) >= 1, c
    assert c.get("brute_force.tile_cut.dispatch{impl=xla}", 0) >= 1, c
    assert "brute_force.tile_cut.dispatch{impl=pallas}" not in c, c
    tile_cut_as_on_tpu()
    c = counters()
    assert c.get("brute_force.tile_cut.dispatch{impl=pallas}", 0) >= 1, c
    assert not any(key.startswith("brute_force.tile_cut.fallback")
                   for key in c), c
