"""K-means tests vs sklearn-style expectations (reference test model:
cpp/test/cluster/kmeans.cu + pylibraft test_kmeans.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.cluster import KMeansParams, KMeansBalancedParams, kmeans, kmeans_balanced
from raft_tpu.cluster import distributed as dkm
from raft_tpu.parallel import make_mesh
from raft_tpu.random import make_blobs
from raft_tpu.random.rng import RngState
import jax


@pytest.fixture(scope="module")
def blobs():
    x, labels = make_blobs(1000, 8, n_clusters=5, cluster_std=0.4)
    return np.asarray(x), np.asarray(labels)


def _cluster_quality(x, labels_true, labels_pred, n_clusters):
    """Adjusted-rand-free sanity: majority label purity per cluster."""
    correct = 0
    for c in range(n_clusters):
        members = labels_true[labels_pred == c]
        if len(members):
            correct += np.bincount(members).max()
    return correct / len(labels_true)


class TestKMeans:
    def test_fit_recovers_blobs(self, blobs):
        x, true = blobs
        params = KMeansParams(n_clusters=5, max_iter=100, seed=1)
        centroids, inertia, n_iter = kmeans.fit(params, jnp.asarray(x))
        assert centroids.shape == (5, 8)
        assert int(n_iter) >= 1
        labels = np.asarray(kmeans.predict(centroids, jnp.asarray(x)))
        assert _cluster_quality(x, true, labels, 5) > 0.95

    def test_inertia_decreases_vs_random(self, blobs):
        x, _ = blobs
        params = KMeansParams(n_clusters=5, max_iter=100, seed=1)
        centroids, inertia, _ = kmeans.fit(params, jnp.asarray(x))
        rand_c = x[np.random.default_rng(0).choice(len(x), 5, replace=False)]
        rand_cost = float(kmeans.cluster_cost(jnp.asarray(rand_c), jnp.asarray(x)))
        assert float(inertia) <= rand_cost

    def test_transform_shape(self, blobs):
        x, _ = blobs
        params = KMeansParams(n_clusters=4, max_iter=20)
        centroids, _, _ = kmeans.fit(params, jnp.asarray(x))
        t = kmeans.transform(centroids, jnp.asarray(x))
        assert t.shape == (len(x), 4)
        # transform distances must agree with predict argmin
        labels = np.asarray(kmeans.predict(centroids, jnp.asarray(x)))
        np.testing.assert_array_equal(np.asarray(t).argmin(1), labels)

    def test_weighted_fit_ignores_zero_weight(self, blobs):
        x, _ = blobs
        # add junk rows with zero weight; fit must be unaffected
        junk = np.full((50, 8), 100.0, np.float32)
        xw = np.concatenate([x, junk])
        w = np.concatenate([np.ones(len(x), np.float32), np.zeros(50, np.float32)])
        params = KMeansParams(n_clusters=5, max_iter=100, seed=3)
        c1, _, _ = kmeans.fit(params, jnp.asarray(xw), sample_weights=jnp.asarray(w))
        assert np.abs(np.asarray(c1)).max() < 50  # junk never became a center

    def test_plus_plus_init_spreads(self, blobs):
        x, _ = blobs
        c = kmeans.init_plus_plus(jax.random.PRNGKey(0), jnp.asarray(x), 5)
        # all 5 seeds distinct
        d = np.asarray(c)
        assert len(np.unique(d.round(6), axis=0)) == 5

    def test_minibatch_fit_recovers_blobs(self, blobs):
        x, labels_true = blobs
        p = KMeansParams(n_clusters=5, seed=3)
        c, inertia, n_iters = kmeans.fit_minibatch(p, jnp.asarray(x),
                                                   batch_size=256)
        assert c.shape == (5, 8) and n_iters > 0
        pred = np.asarray(kmeans.predict(c, jnp.asarray(x)))
        assert _cluster_quality(x, labels_true, pred, 5) > 0.9
        # mini-batch inertia lands near the full-batch fit's
        _, full_inertia, _ = kmeans.fit(p, jnp.asarray(x))
        assert float(inertia) < 2.0 * float(full_inertia) + 1e-3

    def test_update_centroids_step(self, blobs):
        x, _ = blobs
        xj = jnp.asarray(x, jnp.float32)
        c0 = xj[:5]
        labels = kmeans.predict(c0, xj)
        w = jnp.ones((x.shape[0],), jnp.float32)
        counts, c1 = kmeans.update_centroids(xj, w, c0, labels)
        assert counts.shape == (5,) and c1.shape == c0.shape
        np.testing.assert_allclose(float(jnp.sum(counts)), x.shape[0])
        # one exact update step cannot increase the cost
        assert float(kmeans.cluster_cost(c1, xj)) <= float(
            kmeans.cluster_cost(c0, xj)) + 1e-3

    def test_find_k(self):
        x, _ = make_blobs(600, 4, n_clusters=3, cluster_std=0.2, state=RngState(7))
        best_k, inertias = kmeans.find_k(jnp.asarray(np.asarray(x)), k_max=8,
                                         params=KMeansParams(max_iter=50, seed=2))
        assert 2 <= best_k <= 5


class TestKMeansBalanced:
    def test_build_clusters_balance(self, blobs):
        x, _ = blobs
        centers, labels, sizes = kmeans_balanced.build_clusters(
            jnp.asarray(x), 16, KMeansBalancedParams(n_iters=25, seed=1))
        sizes = np.asarray(sizes)
        assert sizes.sum() == len(x)
        assert sizes.max() <= len(x) // 16 * 6  # no degenerate mega-cluster
        assert (sizes > 0).sum() >= 14          # nearly all clusters populated

    def test_hierarchical_fit(self, blobs):
        x, _ = blobs
        centers = kmeans_balanced.fit(jnp.asarray(x), 32,
                                      KMeansBalancedParams(n_iters=20, seed=1))
        assert centers.shape == (32, 8)
        labels = np.asarray(kmeans_balanced.predict(centers, jnp.asarray(x)))
        sizes = np.bincount(labels, minlength=32)
        assert (sizes > 0).sum() >= 28
        assert sizes.max() <= len(x) // 32 * 8

    def test_cosine_metric(self, blobs):
        x, _ = blobs
        p = KMeansBalancedParams(n_iters=15, metric="cosine", seed=2)
        centers = kmeans_balanced.fit(jnp.asarray(x), 8, p)
        norms = np.linalg.norm(np.asarray(centers), axis=1)
        np.testing.assert_allclose(norms, 1.0, rtol=1e-4)


class TestDistributedKMeans:
    def test_matches_single_device(self, blobs):
        x, true = blobs
        mesh = make_mesh(axis_names=("shard",))
        params = KMeansParams(n_clusters=5, max_iter=100, seed=1)
        c0 = kmeans.init_random(jax.random.PRNGKey(0), jnp.asarray(x), 5)
        c_dist, inertia_d, _ = dkm.fit(params, jnp.asarray(x), mesh,
                                       init_centroids=c0)
        c_single, inertia_s, _ = kmeans.fit(params, jnp.asarray(x),
                                            init_centroids=c0,
                                            )
        # same init → same fixpoint (up to fp reduction order)
        np.testing.assert_allclose(np.asarray(inertia_d), np.asarray(inertia_s),
                                   rtol=1e-3)
        # random init may hit a weaker optimum; equivalence with the
        # single-device fixpoint above is the real assertion
        labels = np.asarray(dkm.predict(c_dist, jnp.asarray(x), mesh))
        assert _cluster_quality(x, true, labels, 5) > 0.75

    def test_non_divisible_rows(self):
        x, _ = make_blobs(997, 6, n_clusters=3, cluster_std=0.3)
        mesh = make_mesh(axis_names=("shard",))
        params = KMeansParams(n_clusters=3, max_iter=60, seed=5)
        c, inertia, _ = dkm.fit(params, jnp.asarray(np.asarray(x)), mesh)
        assert np.isfinite(float(inertia))
        labels = dkm.predict(c, jnp.asarray(np.asarray(x)), mesh)
        assert labels.shape == (997,)


def test_balanced_level2_drop_warning():
    """Level-2 sampling truncation past the per-mesocluster cap must be
    surfaced as a warning above the threshold and stay silent below it
    (ADVICE r5) — silent sampling bias is otherwise invisible."""
    from raft_tpu.cluster.kmeans_balanced import _warn_level2_drop
    from raft_tpu.core import logging as rlog

    msgs = []
    rlog.set_callback(lambda lvl, msg: msgs.append(msg))
    try:
        _warn_level2_drop(1, 1000, 504)      # 0.1% — below threshold
        assert not msgs
        _warn_level2_drop(150, 1000, 504)    # 15% — must warn
    finally:
        rlog.set_callback(None)
    assert any("level-2 sampling dropped" in m for m in msgs), msgs


def test_balanced_fit_no_drop_warning_on_blobs():
    """A well-behaved dataset through the full hierarchical fit must not
    trigger the level-2 drop warning (wiring check)."""
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
    from raft_tpu.core import logging as rlog

    x, _ = make_blobs(2000, 8, n_clusters=16, cluster_std=1.0)
    msgs = []
    rlog.set_callback(lambda lvl, msg: msgs.append(msg))
    try:
        kmeans_balanced.fit(jnp.asarray(np.asarray(x)), 64,
                            KMeansBalancedParams(n_iters=4, seed=0))
    finally:
        rlog.set_callback(None)
    assert not any("level-2 sampling dropped" in m for m in msgs), msgs


def _many_small_clusters(seed, n=20_000, dim=64, rows_per_cluster=24):
    """The benchmark's hard_clusters design at the tests' size: n / 24
    Gaussian clusters of equal expected size, each spread 0.45 of its
    nearest other center's distance, in high dimension, where plain
    Lloyd from random rows leaves many lists near empty."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n // rows_per_cluster, dim))
    sq = (centers ** 2).sum(1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * centers @ centers.T
    np.fill_diagonal(d2, np.inf)
    scale = 0.45 * np.sqrt(d2.min(1)) / np.sqrt(dim)
    pick = rng.integers(0, centers.shape[0], n)
    return (centers[pick] + scale[pick, None]
            * rng.normal(size=(n, dim))).astype(np.float32)


@pytest.mark.parametrize("seed", [304, 313, 7])
def test_balanced_fit_bounds(seed):
    """Every list of the hierarchical fit holds between a tenth of the
    mean and twice it, on data of many small clusters."""
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams

    x = jnp.asarray(_many_small_clusters(seed))
    n_lists = 128
    centers = kmeans_balanced.fit(x, n_lists, KMeansBalancedParams())
    sizes = np.bincount(np.asarray(kmeans_balanced.predict(centers, x)),
                        minlength=n_lists)
    mean = x.shape[0] / n_lists
    assert sizes.max() <= 2.0 * mean, sizes.max() / mean
    assert sizes.min() >= 0.1 * mean, sizes.min() / mean


def test_fit_counts_reseeded():
    """``kmeans_balanced.reseeded{level=}``: the flat fit's count is the
    number of clusters its sweeps re-seeded, and the hierarchical fit
    counts each level."""
    from raft_tpu import obs
    from raft_tpu.cluster import kmeans_balanced as kb
    from raft_tpu.cluster.kmeans import init_random
    from raft_tpu.obs.metrics import MetricsRegistry
    from raft_tpu.random.rng import RngState

    x = jnp.asarray(_many_small_clusters(5, n=4000, dim=32))
    params = kb.KMeansBalancedParams(n_iters=10, seed=3)
    reg = MetricsRegistry()
    obs.enable(registry=reg, hbm=False)
    try:
        kb.fit(x, 8, params)
        kb.fit(x, 64, params)
    finally:
        obs.disable()
    counters = reg.snapshot()["counters"]
    key = RngState(params.seed).key()
    _, want = kb._balanced_lloyd(x, jnp.ones((x.shape[0],)),
                                 init_random(key, x, 8), 8, params.n_iters,
                                 key)
    assert counters["kmeans_balanced.reseeded{level=flat}"] == int(want)
    for level in ("meso", "fine", "joint"):
        assert f"kmeans_balanced.reseeded{{level={level}}}" in counters
    assert counters["kmeans_balanced.reseeded{level=fine}"] > 0


def test_split_fattest_splits_the_largest_for_the_smallest():
    """Three clusters are starved (under a quarter of the mean 100) and
    at most half of the five are re-seeded: the two smallest take the
    two largest ones' centers, each pair set apart by twice the donor's
    step; the rest stay put."""
    from raft_tpu.cluster.kmeans_balanced import _split_fattest

    centers = jnp.arange(5 * 3, dtype=jnp.float32).reshape(5, 3)
    counts = jnp.array([1.0, 400.0, 20.0, 2.0, 177.0])
    step = jnp.full((5, 3), 0.25, jnp.float32) * jnp.arange(1, 6)[:, None]
    out, n = _split_fattest(centers, counts, jnp.ones(5, bool), step,
                            jnp.float32(100.0))
    out, c, step = np.asarray(out), np.asarray(centers), np.asarray(step)
    assert int(n) == 2
    # the smallest (0) splits the largest (1); the next (3) the next (4)
    for small, big in ((0, 1), (3, 4)):
        np.testing.assert_allclose(out[small], c[big] + step[big])
        np.testing.assert_allclose(out[big], c[big] - step[big])
    np.testing.assert_array_equal(out[2], c[2])
