"""IVF-Flat without a list cap, against a plain reference.

The deployment the benchmark's ``sift1m-ivfflat-nlist1024`` cells run
(raft-ann-bench's ``raft_ivf_flat``): every row kept once, in the list
of its nearest center, lists as long as they come, and the answer the
exact top-k of the rows of each query's ``n_probes`` nearest lists. The
reference here is straightforward ``jax.numpy`` in float32 with every
matmul at ``Precision.HIGHEST``, no kernels: a plain argmin over the
index's centers for each row, and a plain sort over the union of the
probed lists' rows for each query. It takes the centers from the index
(they are the fit's, which the reference does not redo) and nothing
else.

Tolerances, each with its reason:

- ``ASSIGN_RTOL`` (1e-5): a row may sit in another list than the
  reference's argmin only where its distances to the two centers agree
  to float32 rounding of the expanded form (``|x|^2 + |c|^2 - 2 x.c``
  with ``|x|^2`` near 30 here, about 1e-6 of it), a tie;
- ``DIST_RTOL`` (5e-5): a returned distance against the distance of
  the same row to the same query in difference form: the program's is
  a float32 expanded form at HIGHEST, off by rounding alone (at most
  8.4e-6 here, on the grouped path); a bfloat16 contraction, one pass
  of the MXU's default precision, is off by 4e-3 to 5e-2 and fails it
  (``test_bf16_contraction_fails_the_comparison``);
- ids: the same set as the reference's, except where the reference's
  k-th and (k+1)-th distances tie within ``DIST_RTOL``.

The sizes make the lists differ in length (clusters of uneven size), at
k 10, on both search paths, with the Pallas kernels in interpret mode as
on a TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from raft_tpu import obs
from raft_tpu.neighbors import ivf_flat
from raft_tpu.neighbors.ivf_flat import IndexParams, SearchParams
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.ops import pallas_kernels as pk

K, N_LISTS, N_PROBES = 10, 32, 6
ASSIGN_RTOL = 1e-5
DIST_RTOL = 5e-5
HIGHEST = lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def data():
    """4,000 rows of 32 dims in 40 Gaussian clusters of uneven size, and
    64 queries from the same clusters."""
    rng = np.random.default_rng(28)
    centers = rng.normal(0.0, 1.0, (40, 32))
    weights = rng.uniform(0.2, 1.0, 40)
    weights /= weights.sum()

    def draw(m):
        c = rng.choice(40, m, p=weights)
        return (centers[c] + 0.4 * rng.normal(size=(m, 32))).astype(
            np.float32)

    return draw(4000), draw(64)


@pytest.fixture(scope="module")
def index(data):
    x, _ = data
    return ivf_flat.build(jnp.asarray(x), IndexParams(
        n_lists=N_LISTS, list_size_cap_factor=None, seed=0))


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Dispatch as on a TPU, every Pallas kernel in interpret mode."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    pltpu.set_tpu_interpret_mode()
    try:
        yield
    finally:
        pltpu.set_tpu_interpret_mode(None)
        jax.clear_caches()


# -- the plain reference ----------------------------------------------------

def _sq_dist(a, b, precision=HIGHEST):
    """``[m, n]`` squared L2 distances in float32, expanded form."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    g = jnp.dot(a, b.T, precision=precision)
    return (jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :]
            - 2.0 * g)


def nearest_centers(x, centers):
    """Each row's nearest center, and its distances to all of them."""
    d = _sq_dist(x, centers)
    return np.asarray(jnp.argmin(d, axis=1)), np.asarray(d)


def probed_topk(x, queries, centers, k, n_probes, precision=HIGHEST):
    """The exact top-``k`` (distances, ids) of each query over the rows
    of its ``n_probes`` nearest lists, a row's list being its nearest
    center; ``k + 1`` columns, the last for the tie check."""
    assign, _ = nearest_centers(x, centers)
    probes = np.argsort(np.asarray(_sq_dist(queries, centers)), axis=1,
                        kind="stable")[:, :n_probes]
    d = np.asarray(_sq_dist(queries, x, precision))
    probed = (assign[None, :, None] == probes[:, None, :]).any(axis=2)
    d = np.where(probed, d, np.inf)
    order = np.argsort(d, axis=1, kind="stable")[:, :k + 1]
    return np.take_along_axis(d, order, axis=1), order


def compare(dist, ids, x, queries, ref_dist, ref_ids, k):
    """The failures of an answer against the reference: ids outside the
    reference's set (boundary ties excepted) and distances off by more
    than ``DIST_RTOL`` from the reference's distance of the same row."""
    dist, ids = np.asarray(dist), np.asarray(ids)
    fails = []
    row_d = np.asarray(jnp.sum(
        (jnp.asarray(queries)[:, None, :] - jnp.asarray(x)[ids]) ** 2, 2))
    gap = np.abs(dist - row_d) / np.maximum(row_d, 1e-30)
    if gap.max() > DIST_RTOL:
        fails.append(f"distance off by {gap.max():.2e}")
    for j in range(ids.shape[0]):
        kth, next_ = ref_dist[j, k - 1], ref_dist[j, k]
        tie = abs(next_ - kth) <= DIST_RTOL * max(kth, 1e-30)
        if not tie and set(ids[j]) != set(ref_ids[j, :k]):
            fails.append(f"query {j}: ids differ")
    return fails


# -- the index --------------------------------------------------------------

def test_every_row_in_its_nearest_list(data, index):
    x, _ = data
    sizes = np.asarray(index.list_sizes)
    ids = np.asarray(index.packed_ids)
    held = np.sort(ids[ids >= 0])
    np.testing.assert_array_equal(held, np.arange(x.shape[0]))
    assert index.size == x.shape[0]
    assert sizes.max() > 2 * sizes.min()             # lists differ
    assert index.max_list_size == ivf_flat._lane_round(int(sizes.max()))
    assign, d = nearest_centers(x, index.centers)
    for lst in range(N_LISTS):
        rows = ids[lst][ids[lst] >= 0]
        assert rows.size == sizes[lst]
        off = rows[assign[rows] != lst]
        own = d[off, lst]
        best = d[off, assign[off]]
        assert np.all(own - best <= ASSIGN_RTOL * np.maximum(best, 1.0)), (
            lst, off)


@pytest.mark.parametrize("scan_mode", ["per_query", "grouped"])
def test_search_matches_the_reference(data, index, tpu_dispatch, scan_mode):
    x, q = data
    dist, ids = ivf_flat.search(index, jnp.asarray(q), K, SearchParams(
        n_probes=N_PROBES, scan_mode=scan_mode))
    ref_dist, ref_ids = probed_topk(x, q, index.centers, K, N_PROBES)
    assert compare(dist, ids, x, q, ref_dist, ref_ids, K) == []


def test_bf16_contraction_fails_the_comparison(data, index):
    """The reference itself, with its query-row contraction in one
    bfloat16 pass (inputs rounded to bfloat16, as the MXU's default
    precision takes them), fails the comparison that the program
    passes."""
    x, q = data
    ref_dist, ref_ids = probed_topk(x, q, index.centers, K, N_PROBES)

    def bf16(a):
        return lax.reduce_precision(jnp.asarray(a, jnp.float32),
                                    exponent_bits=8, mantissa_bits=7)

    low_q, low_x = bf16(q), bf16(x)
    d = np.asarray(jnp.sum(q * q, 1)[:, None] + jnp.sum(x * x, 1)[None, :]
                   - 2.0 * jnp.dot(low_q, low_x.T, precision=HIGHEST))
    assign, _ = nearest_centers(x, index.centers)
    probes = np.argsort(np.asarray(_sq_dist(q, index.centers)), 1)[
        :, :N_PROBES]
    d = np.where((assign[None, :, None] == probes[:, None, :]).any(2), d,
                 np.inf)
    low_ids = np.argsort(d, axis=1)[:, :K]
    low_dist = np.take_along_axis(d, low_ids, axis=1)
    assert compare(low_dist, low_ids, x, q, ref_dist, ref_ids, K) != []


# -- no cap: the build's other paths -------------------------------------------

def test_uncapped_refuses_spill(data):
    x, _ = data
    with pytest.raises(Exception, match="spill"):
        ivf_flat.build(jnp.asarray(x), IndexParams(
            n_lists=8, list_size_cap_factor=None, spill=True))


def test_uncapped_extend_and_empty_build_keep_every_row(data):
    x = data[0][:2000]
    half = x.shape[0] // 2
    params = IndexParams(n_lists=16, list_size_cap_factor=None, seed=0)
    grown = ivf_flat.extend(ivf_flat.build(jnp.asarray(x[:half]), params),
                            jnp.asarray(x[half:]))
    empty = ivf_flat.build(jnp.asarray(x), IndexParams(
        n_lists=16, list_size_cap_factor=None, add_data_on_build=False))
    assert empty.max_list_size == 8 and empty.size == 0
    filled = ivf_flat.extend(empty, jnp.asarray(x))
    for idx in (grown, filled):
        ids = np.asarray(idx.packed_ids)
        np.testing.assert_array_equal(np.sort(ids[ids >= 0]),
                                      np.arange(x.shape[0]))
        assert idx.max_list_size >= int(np.asarray(idx.list_sizes).max())


def test_build_records_list_fill(data):
    """The gauge ``ivf.build.list_fill{index=ivf_flat}`` holds the rows
    the index holds over its padded slots."""
    x, _ = data
    reg = MetricsRegistry()
    obs.enable(registry=reg, hbm=False)
    try:
        idx = ivf_flat.build(jnp.asarray(x), IndexParams(
            n_lists=N_LISTS, list_size_cap_factor=None, seed=0))
    finally:
        obs.disable()
    fill = reg.snapshot()["gauges"]["ivf.build.list_fill{index=ivf_flat}"]
    assert fill == pytest.approx(
        x.shape[0] / (N_LISTS * idx.max_list_size))
    assert 0 < fill <= 1
