"""Native components + bench harness
(reference: cpp/bench/ann dataset/driver; refine_host-inl.hpp).
"""

import os

import numpy as np
import pytest

from raft_tpu import native
from raft_tpu.bench import dataset as ds_mod
from raft_tpu.bench import runner


def test_bin_roundtrip(tmp_path, rng):
    a = rng.random((50, 9), dtype=np.float32)
    p = str(tmp_path / "x.fbin")
    native.bin_write(p, a)
    assert native.bin_header(p) == (50, 9)
    np.testing.assert_array_equal(native.bin_read(p, np.float32), a)
    np.testing.assert_array_equal(native.bin_read(p, np.float32, offset=7, count=11), a[7:18])


def test_bin_read_out_of_range(tmp_path, rng):
    p = str(tmp_path / "y.fbin")
    native.bin_write(p, rng.random((10, 4), dtype=np.float32))
    with pytest.raises(IOError):
        native.bin_read(p, np.float32, offset=5, count=20)


@pytest.mark.skipif(not native.available(), reason="native lib unavailable")
def test_refine_host_matches_numpy(rng):
    x = rng.random((300, 12), dtype=np.float32)
    q = rng.random((15, 12), dtype=np.float32)
    cand = rng.integers(0, 300, (15, 40)).astype(np.int32)
    cand[0, :5] = -1  # invalid slots
    d, i = native.refine_host(x, q, cand, k=6, metric="sqeuclidean")
    full = ((q[:, None, :] - x[np.maximum(cand, 0)]) ** 2).sum(-1)
    full[cand < 0] = np.inf
    pos = np.argsort(full, axis=1)[:, :6]
    want_i = np.take_along_axis(cand, pos, 1)
    want_d = np.take_along_axis(full, pos, 1)
    np.testing.assert_allclose(np.sort(d, 1), np.sort(want_d, 1), rtol=1e-5)
    assert np.array_equal(np.sort(i, 1), np.sort(want_i, 1))


@pytest.mark.skipif(not native.available(), reason="native lib unavailable")
def test_refine_host_inner_product(rng):
    x = rng.random((100, 8), dtype=np.float32)
    q = rng.random((5, 8), dtype=np.float32)
    cand = np.tile(np.arange(100, dtype=np.int32), (5, 1))
    d, i = native.refine_host(x, q, cand, k=3, metric="inner_product")
    full = q @ x.T
    want_i = np.argsort(-full, axis=1)[:, :3]
    assert np.array_equal(np.sort(i, 1), np.sort(want_i, 1))
    assert (np.diff(d, axis=1) <= 1e-6).all()  # descending similarity


def test_dataset_write_load(tmp_path, rng):
    ds = ds_mod.make_synthetic("t", 200, 8, 20, seed=1)
    ds_mod.compute_groundtruth(ds, k=10)
    ds_mod.write_dataset(str(tmp_path), ds)
    back = ds_mod.load_dataset(str(tmp_path), "t")
    np.testing.assert_array_equal(back.base, ds.base)
    np.testing.assert_array_equal(back.groundtruth, ds.groundtruth)
    sub = ds_mod.load_dataset(str(tmp_path), "t", max_rows=50)
    assert sub.base.shape == (50, 8)


def test_runner_end_to_end():
    config = {
        "dataset": {"name": "tiny", "n": 2000, "dim": 16, "n_queries": 100},
        "k": 5,
        "batch_size": 100,
        "index": [
            {"name": "bf", "algo": "brute_force", "build_param": {},
             "search_params": [{}]},
            {"name": "ivf", "algo": "ivf_flat",
             "build_param": {"n_lists": 8},
             "search_params": [{"n_probes": 4}, {"n_probes": 8}]},
        ],
    }
    results = runner.run_config(config, verbose=False)
    assert len(results) == 3
    bf = results[0]
    assert bf.recall == pytest.approx(1.0)
    assert bf.qps > 0 and bf.build_s >= 0
    # full-probe ivf over clustered data must be near-exact
    assert results[2].recall > 0.95
    front = runner.pareto_frontier(results)
    assert front and all(front[i].qps <= front[i + 1].qps for i in range(len(front) - 1))


def test_runner_memmap_dir_chunked_build(tmp_path):
    """The DEEP-100M-shaped path at subset scale: on-disk dataset dir,
    memmapped base, chunked IVF-PQ build (reference: run/conf/deep-1B.json
    + dataset.hpp subsets)."""
    # no groundtruth on disk: the runner recomputes it on the subset
    ds = ds_mod.make_synthetic("deep-shaped", 4000, 32, 100, seed=3)
    ds_mod.write_dataset(str(tmp_path), ds)
    config = {
        "dataset": {"dir": str(tmp_path), "name": "deep-shaped",
                    "metric": "sqeuclidean", "mmap": True, "max_rows": 3000},
        "k": 10,
        "batch_size": 100,
        "index": [
            {"name": "ivf_pq.chunked", "algo": "ivf_pq",
             "build_param": {"n_lists": 16, "pq_dim": 16,
                             "chunked_build": True, "chunk_rows": 512},
             "search_params": [{"n_probes": 16}]},
        ],
    }
    results = runner.run_config(config, verbose=False)
    assert len(results) == 1
    assert results[0].qps > 0
    assert results[0].recall >= 0.5


def test_subset_load_drops_full_groundtruth(tmp_path, rng):
    """GT computed over the full base is unreachable on a subset — it must
    be dropped so callers recompute, not silently deflate recall."""
    ds = ds_mod.make_synthetic("g", 300, 8, 10, seed=2)
    ds_mod.compute_groundtruth(ds, k=5)
    ds_mod.write_dataset(str(tmp_path), ds)
    full = ds_mod.load_dataset(str(tmp_path), "g")
    assert full.groundtruth is not None
    sub = ds_mod.load_dataset(str(tmp_path), "g", max_rows=100)
    assert sub.groundtruth is None


def test_refine_gathered_matches_device(rng):
    """Host-gather refine (memmap path) must equal the device refine."""
    import jax.numpy as jnp
    from raft_tpu.neighbors import refine

    x = rng.random((500, 16), dtype=np.float32)
    q = rng.random((20, 16), dtype=np.float32)
    cand = rng.integers(0, 500, (20, 30)).astype(np.int32)
    cand[0, 5] = -1  # invalid slot
    d1, i1 = refine.refine(jnp.asarray(x), jnp.asarray(q),
                           jnp.asarray(cand), 10)
    d2, i2 = refine.refine_gathered(x, jnp.asarray(q), cand, 10)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


def test_deep100m_conf_parses():
    import json, os
    conf = os.path.join(os.path.dirname(runner.__file__), "conf",
                        "deep-100m.json")
    with open(conf) as f:
        cfg = json.load(f)
    assert cfg["dataset"]["mmap"] is True
    assert cfg["index"][0]["build_param"]["chunked_build"] is True


def test_runner_rejects_unknown_algo():
    with pytest.raises(ValueError):
        runner.run_config(
            {"dataset": {"name": "x", "n": 100, "dim": 4, "n_queries": 5},
             "index": [{"algo": "hnsw"}]},
            verbose=False,
        )


def test_export_csv(tmp_path):
    rows = [runner.BenchResult("bf", "bf", "d", 10, 100, 1.0, 0.1, 1000.0, 0.99)]
    p = str(tmp_path / "out.csv")
    runner.export_csv(rows, p)
    text = open(p).read()
    assert "qps" in text and "1000.0" in text


def test_hdf5_ingest_roundtrip(tmp_path, rng):
    """convert_hdf5 writes a loadable dataset dir (reference:
    get_dataset/__main__.py:34 convert_hdf5_to_fbin)."""
    import h5py

    from raft_tpu.bench import ingest

    base = rng.random((100, 8), dtype=np.float32)
    q = rng.random((10, 8), dtype=np.float32)
    nb = rng.integers(0, 100, (10, 5)).astype(np.int32)
    h5 = tmp_path / "toy-8-angular.hdf5"
    with h5py.File(h5, "w") as f:
        f["train"] = base
        f["test"] = q
        f["neighbors"] = nb
        f["distances"] = rng.random((10, 5), dtype=np.float32)
    d = ingest.convert_hdf5(str(h5), str(tmp_path), normalize=True)
    assert d.endswith("toy-8-inner")  # angular → inner rename
    ds = ds_mod.load_dataset(str(tmp_path), "toy-8-inner")
    norm = base / np.linalg.norm(base, axis=1, keepdims=True)
    np.testing.assert_allclose(ds.base, norm, rtol=1e-6)
    np.testing.assert_array_equal(ds.groundtruth, nb)


def test_split_groundtruth(tmp_path, rng):
    """big-ann gt binary → ibin/fbin pair (reference: split_groundtruth)."""
    import struct

    from raft_tpu.bench import ingest

    ids = rng.integers(0, 1000, (20, 10)).astype(np.int32)
    dist = rng.random((20, 10), dtype=np.float32)
    gt = tmp_path / "gt.bin"
    with open(gt, "wb") as f:
        f.write(struct.pack("<ii", 20, 10))
        f.write(ids.tobytes())
        f.write(dist.tobytes())
    out = ingest.split_groundtruth(str(gt))
    got_ids = native.bin_read(os.path.join(out, "groundtruth.ibin"), np.int32)
    got_d = native.bin_read(os.path.join(out, "groundtruth_dist.fbin"),
                            np.float32)
    np.testing.assert_array_equal(got_ids, ids)
    np.testing.assert_allclose(got_d, dist)


def test_plot_outputs(tmp_path):
    """plot module renders search + build figures from a results CSV
    (reference: plot/__main__.py)."""
    from raft_tpu.bench import plot as plot_mod

    rows = [runner.BenchResult(
        algo="ivf_flat", index_name=f"ivf.{i}", dataset="toy", k=10,
        batch_size=100, build_s=1.0 + i, search_s=0.1, qps=1000.0 * (i + 1),
        recall=0.9 + 0.03 * i, search_param={"n_probes": 2 ** i})
        for i in range(3)]
    csv_path = tmp_path / "res.csv"
    runner.export_csv(rows, str(csv_path))
    back = plot_mod.read_csv(str(csv_path))
    assert len(back) == 3 and back[0].search_param == {"n_probes": 1}
    out = plot_mod.plot_search(back, str(tmp_path / "s.png"))
    assert os.path.getsize(out) > 1000
    out2 = plot_mod.plot_build(back, str(tmp_path / "b.png"))
    assert os.path.getsize(out2) > 1000


def test_chunked_groundtruth_matches_exact(rng):
    """The streaming GT path (memmap-scale bases) must agree with the
    in-HBM brute force path."""
    base = rng.random((5000, 16), dtype=np.float32)
    q = rng.random((300, 16), dtype=np.float32)
    ds = ds_mod.Dataset(name="t", base=base, queries=q)
    ds_mod.compute_groundtruth(ds, k=10, device_budget=1, chunk_rows=1024,
                               max_queries=200)
    d = ((q[:200, :, None] - base.T[None]) ** 2).sum(1)
    exact = np.argsort(d, axis=1)[:, :10]
    got = ds.groundtruth
    assert got.shape == (200, 10)
    # allow distance ties to permute ids: compare via distances
    dg = np.take_along_axis(d, got, axis=1)
    de = np.take_along_axis(d, exact, axis=1)
    np.testing.assert_allclose(np.sort(dg, 1), np.sort(de, 1),
                               rtol=1e-4, atol=1e-4)


def test_native_library_keyed_by_source_and_flags(monkeypatch, tmp_path):
    """Only a binary built from this source with these portable flags is
    ever loaded: the file name changes with either, so a stale or
    foreign build sitting in the package directory is never picked up."""
    assert "-march=native" not in native._FLAGS
    name = native._so_path()
    assert os.path.basename(name).startswith("libraft_tpu_native-")
    monkeypatch.setattr(native, "_FLAGS", native._FLAGS + ("-DCHANGED",))
    assert native._so_path() != name
    monkeypatch.undo()
    src = tmp_path / "raft_tpu_native.cpp"
    src.write_bytes(open(native._SRC, "rb").read() + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    assert native._so_path() != name
