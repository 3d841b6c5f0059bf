"""Pallas kernels in interpreter mode vs references (the CPU-side
equivalent of the reference's kernel unit tests; on real TPU the same
kernels run compiled — see bench.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.ops import fused_l2_argmin, select_k_pallas


def test_fused_l2_argmin_interpret(rng):
    x = rng.random((100, 40), dtype=np.float32)
    y = rng.random((1000, 40), dtype=np.float32)
    d, i = fused_l2_argmin(jnp.asarray(x), jnp.asarray(y), interpret=True)
    full = ((x[:, None, :] - y[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(i), np.argmin(full, 1))
    np.testing.assert_allclose(np.asarray(d), full.min(1), rtol=1e-4, atol=1e-5)


def test_fused_l2_argmin_ragged_shapes(rng):
    # shapes not multiples of the block sizes exercise the padding masks
    x = rng.random((33, 7), dtype=np.float32)
    y = rng.random((517, 7), dtype=np.float32)
    d, i = fused_l2_argmin(jnp.asarray(x), jnp.asarray(y), bm=32, bn=256, interpret=True)
    full = ((x[:, None, :] - y[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(i), np.argmin(full, 1))


@pytest.mark.parametrize("select_min", [True, False])
def test_select_k_pallas_interpret(rng, select_min):
    s = rng.random((37, 5000), dtype=np.float32)
    v, ix = select_k_pallas(jnp.asarray(s), 10, select_min=select_min, interpret=True)
    order = np.argsort(s if select_min else -s, 1)[:, :10]
    want_v = np.take_along_axis(s, order, 1)
    np.testing.assert_allclose(np.asarray(v), want_v, rtol=1e-6)
    np.testing.assert_array_equal(np.sort(np.asarray(ix), 1), np.sort(order, 1))


def test_select_k_pallas_duplicates(rng):
    # ties: every extracted index must be distinct
    s = np.zeros((4, 300), np.float32)
    v, ix = select_k_pallas(jnp.asarray(s), 8, interpret=True)
    ix = np.asarray(ix)
    for r in range(4):
        assert len(set(ix[r].tolist())) == 8
    np.testing.assert_allclose(np.asarray(v), 0.0)


def test_select_k_pallas_k_too_big(rng):
    with pytest.raises(ValueError):
        select_k_pallas(jnp.zeros((2, 5)), 6, interpret=True)


def test_fused_dispatch_cpu_falls_back(rng):
    # on the CPU test backend the auto dispatch must take the XLA path
    from raft_tpu.distance.fused_l2_nn import fused_l2_nn_argmin

    x = rng.random((20, 8), dtype=np.float32)
    y = rng.random((50, 8), dtype=np.float32)
    d, i = fused_l2_nn_argmin(jnp.asarray(x), jnp.asarray(y))
    full = ((x[:, None, :] - y[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(i), np.argmin(full, 1))


class TestBinTwoBest:
    """bin_two_best (the exact search's per-tile bin cut on a TPU) in
    interpret mode against the XLA two-pass cut it replaces: values
    and positions equal, ties and infinite bins included."""

    @staticmethod
    def _planted(rng, m, it, select_min):
        worst = np.inf if select_min else -np.inf
        # five distinct values: equal entries share most bins
        x = rng.integers(0, 5, size=(m, it)).astype(np.float32)
        x[:, 3::128] = 2.0              # a bin whose entries are all equal
        x[:, 9::128] = worst            # a bin of padding alone
        x[:, 17::128][::3] = -worst     # the other infinity, on some rows
        x[:, it - it // 8:] = worst     # padded tail columns
        return x

    @pytest.mark.parametrize("select_min", [True, False])
    @pytest.mark.parametrize("it", [128, 1024, 16384])
    @pytest.mark.parametrize("m", [1, 7, 200, 1000])
    def test_matches_the_xla_cut(self, rng, m, it, select_min):
        from raft_tpu.neighbors import brute_force as bf
        from raft_tpu.ops import pallas_kernels as pk

        assert not pk._on_tpu()  # the module's cut is the XLA one here
        x = jnp.asarray(self._planted(rng, m, it, select_min))
        v, pos = pk.bin_two_best(x, select_min, interpret=True)
        want_v, want_pos = bf._two_best_per_bin(x, select_min)
        assert v.shape == pos.shape == (m, 256)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(want_v))
        np.testing.assert_array_equal(np.asarray(pos), np.asarray(want_pos))

    @pytest.mark.parametrize("m,it,want", [
        (10_000, 16_384, (400, 2048)),   # the benchmark's tile: no pad
        (1000, 16_384, (512, 2048)),     # no divisor near the cap: ragged
        (8, 128 * 7, (8, 896)),
        (4096, 128 * 17, (512, 128)),    # a prime count of chunks
    ])
    def test_blocks(self, m, it, want):
        from raft_tpu.ops import pallas_kernels as pk

        tm, tc = pk._bin_blocks(m, it)
        assert (tm, tc) == want
        assert tm % 8 == 0 and it % tc == 0 and tm * tc * 4 <= (4 << 20)

    def test_row_must_be_lane_aligned(self):
        from raft_tpu.ops import pallas_kernels as pk

        with pytest.raises(ValueError):
            pk.bin_two_best(jnp.zeros((8, 200)), True, interpret=True)


class TestSegmentedScan:
    """segmented_scan_topk (interpret mode off-TPU) vs numpy reference:
    per-strided-bin mins (bin = position mod 128) of each segment's
    distance row."""

    def test_bin_mins_match_numpy(self):
        from raft_tpu.ops.pallas_kernels import segmented_scan_topk

        rng = np.random.default_rng(0)
        n_lists, L, d, n_seg, S = 8, 1408, 64, 12, 16
        packed = rng.standard_normal((n_lists, L, d)).astype(np.float32)
        ids = rng.integers(-1, 10_000, (n_lists, L)).astype(np.int32)
        seg_list = rng.integers(0, n_lists, n_seg).astype(np.int32)
        qv = rng.standard_normal((n_seg, S, d)).astype(np.float32)

        keys, pos = segmented_scan_topk(
            jnp.asarray(seg_list), jnp.asarray(qv), jnp.asarray(packed),
            jnp.asarray(ids), "l2", interpret=True)
        keys, pos = np.asarray(keys), np.asarray(pos)
        T = L // 128
        assert keys.shape == (n_seg, S, 256)

        for s in (0, 5, n_seg - 1):
            li = seg_list[s]
            dist = ((qv[s][:, None, :] - packed[li][None, :, :]) ** 2).sum(-1)
            dist[:, ids[li] < 0] = np.inf
            d3 = dist.reshape(S, T, 128)
            m1 = d3.min(axis=1)                            # [S, 128] bins
            a1 = d3.argmin(axis=1)
            d3b = d3.copy()
            d3b[np.arange(S)[:, None], a1, np.arange(128)[None, :]] = np.inf
            m2 = d3b.min(axis=1)
            a2 = d3b.argmin(axis=1)
            ref_min = np.concatenate([m1, m2], axis=1)
            np.testing.assert_allclose(keys[s], ref_min, rtol=1e-4, atol=1e-4)
            lanes = np.arange(128)[None, :]
            ref_pos = np.concatenate([a1 * 128 + lanes, a2 * 128 + lanes], 1)
            ref_ids = ids[li][ref_pos]                     # kernel emits ids
            okmask = np.isfinite(ref_min)
            assert (pos[s][okmask] == ref_ids[okmask]).all()
            assert (pos[s][~okmask] == -1).all()

    def test_ip_metric(self):
        from raft_tpu.ops.pallas_kernels import segmented_scan_topk

        rng = np.random.default_rng(1)
        packed = rng.standard_normal((4, 256, 32)).astype(np.float32)
        ids = np.where(rng.random((4, 256)) < 0.1, -1, 1).astype(np.int32)
        seg_list = np.array([2, 0, 3], np.int32)
        qv = rng.standard_normal((3, 8, 32)).astype(np.float32)
        keys, pos = segmented_scan_topk(
            jnp.asarray(seg_list), jnp.asarray(qv), jnp.asarray(packed),
            jnp.asarray(ids), "ip", interpret=True)
        keys, pos = np.asarray(keys), np.asarray(pos)
        s = 0
        score = -(qv[s] @ packed[2].T)
        score[:, ids[2] < 0] = np.inf
        ref = score.reshape(8, 2, 128).min(axis=1)
        np.testing.assert_allclose(keys[s][:, :128], ref, rtol=1e-4, atol=1e-4)


class TestIvfPqLutScan:
    """ivfpq_lut_scan_topk (interpret mode) vs a numpy ADC reference:
    in-kernel unpack of packed pq_bits codes, Σ_s QLUT[s, code_s]
    accumulation, masked list tails, and the 2-deep bin running merge."""

    def _mk(self, rng, n_lists, L, S, pq_bits, P, n_seg, seg, sizes=None,
            fold=False):
        from raft_tpu.neighbors.ivf_pq import pack_bits_np

        K = 1 << pq_bits
        rot = S * P
        codes = rng.integers(0, K, (n_lists, L, S)).astype(np.uint8)
        packed = np.stack([pack_bits_np(codes[li], pq_bits)
                           for li in range(n_lists)])
        if fold:
            nb = packed.shape[-1]
            assert (L * nb) % 128 == 0
            packed = packed.reshape(n_lists, -1, 128)
        cb = rng.standard_normal((S, K, P)).astype(np.float32)
        ids = np.full((n_lists, L), -1, np.int32)
        if sizes is None:
            sizes = [L] * n_lists
        for li, sz in enumerate(sizes):
            # unique ids per list: the parity checks key by id
            ids[li, :sz] = li * L + rng.permutation(L)[:sz]
        norms = rng.random((n_lists, L)).astype(np.float32) + 0.5
        ctr = rng.standard_normal((n_lists, rot)).astype(np.float32)
        qv = rng.standard_normal((n_seg, seg, rot)).astype(np.float32)
        seg_list = rng.integers(0, n_lists, n_seg).astype(np.int32)
        return codes, packed, cb, ids, norms, ctr, qv, seg_list

    def _ref_keys(self, codes, cb, ids, norms, ctr, qv, li, s, metric):
        """All-candidate reference: {id: key} for segment s over list li."""
        S = codes.shape[-1]
        dec = cb[np.arange(S)[:, None], codes[li].T].transpose(1, 0, 2)
        dec = dec.reshape(codes.shape[1], -1)             # [L, rot]
        qd = qv[s] @ dec.T                                # [seg, L]
        qc = qv[s] @ ctr[li]                              # [seg]
        if metric == "ip":
            key = -(qc[:, None] + qd)
        else:
            key = norms[li][None, :] - 2.0 * (qc[:, None] + qd)
        return key

    @pytest.mark.parametrize("pq_bits", [4, 5, 6, 8])
    def test_unpack_and_adc_parity(self, pq_bits):
        """L ≤ bins → the emitted candidate set is LOSSLESS: every valid
        candidate appears exactly once with its exact ADC key."""
        from raft_tpu.ops.pallas_kernels import ivfpq_lut_scan_topk

        rng = np.random.default_rng(3 + pq_bits)
        n_lists, L, S, P, n_seg, seg = 4, 256, 16, 2, 5, 8
        codes, packed, cb, ids, norms, ctr, qv, seg_list = self._mk(
            rng, n_lists, L, S, pq_bits, P, n_seg, seg,
            sizes=[L, L - 37, 3, 0])
        keys, kids = ivfpq_lut_scan_topk(
            jnp.asarray(seg_list), jnp.asarray(qv), jnp.asarray(packed),
            jnp.asarray(ids), jnp.asarray(norms), jnp.asarray(ctr),
            jnp.asarray(cb), "l2", pq_bits=pq_bits, pq_dim=S, L=L,
            lut_dtype="float32", interpret=True)
        keys, kids = np.asarray(keys), np.asarray(kids)
        assert keys.shape == (n_seg, seg, 256)
        for s in (0, 2, n_seg - 1):
            li = seg_list[s]
            ref = self._ref_keys(codes, cb, ids, norms, ctr, qv, li, s,
                                 "l2")
            for q in range(seg):
                got = {int(i): k for i, k in zip(kids[s, q], keys[s, q])
                       if i >= 0}
                want = {int(ids[li, l]): ref[q, l]
                        for l in range(L) if ids[li, l] >= 0}
                assert set(got) == set(want)
                for i in want:
                    np.testing.assert_allclose(got[i], want[i],
                                               rtol=1e-4, atol=1e-4)

    def test_folded_layout_parity(self):
        """Lane-folded packed codes (codes_folded storage) decode
        identically — the fold-group strided unpack and bin spreading."""
        from raft_tpu.ops.pallas_kernels import ivfpq_lut_scan_topk

        rng = np.random.default_rng(7)
        # S=16, pq_bits=8 → nb=16 → G=8 fold groups per 128-byte row
        n_lists, L, S, P, n_seg, seg = 3, 240, 16, 2, 4, 8
        codes, packed, cb, ids, norms, ctr, qv, seg_list = self._mk(
            rng, n_lists, L, S, 8, P, n_seg, seg,
            sizes=[L, 100, 17], fold=True)
        assert packed.shape[-1] == 128
        keys, kids = ivfpq_lut_scan_topk(
            jnp.asarray(seg_list), jnp.asarray(qv), jnp.asarray(packed),
            jnp.asarray(ids), jnp.asarray(norms), jnp.asarray(ctr),
            jnp.asarray(cb), "l2", pq_bits=8, pq_dim=S, L=L,
            lut_dtype="float32", interpret=True)
        keys, kids = np.asarray(keys), np.asarray(kids)
        for s in range(n_seg):
            li = seg_list[s]
            ref = self._ref_keys(codes, cb, ids, norms, ctr, qv, li, s,
                                 "l2")
            for q in (0, seg - 1):
                got = {int(i): k for i, k in zip(kids[s, q], keys[s, q])
                       if i >= 0}
                want = {int(ids[li, l]): ref[q, l]
                        for l in range(L) if ids[li, l] >= 0}
                assert set(got) == set(want)
                for i in want:
                    np.testing.assert_allclose(got[i], want[i],
                                               rtol=1e-4, atol=1e-4)

    def test_two_deep_bins_lossy_tail(self):
        """L > bins: each bin keeps the TWO smallest of its strided
        candidates (unfolded mapping: bin = position mod 128)."""
        from raft_tpu.ops.pallas_kernels import ivfpq_lut_scan_topk

        rng = np.random.default_rng(11)
        n_lists, L, S, P, n_seg, seg = 2, 512, 16, 2, 3, 8
        codes, packed, cb, ids, norms, ctr, qv, seg_list = self._mk(
            rng, n_lists, L, S, 8, P, n_seg, seg)
        keys, kids = ivfpq_lut_scan_topk(
            jnp.asarray(seg_list), jnp.asarray(qv), jnp.asarray(packed),
            jnp.asarray(ids), jnp.asarray(norms), jnp.asarray(ctr),
            jnp.asarray(cb), "l2", pq_bits=8, pq_dim=S, L=L,
            lut_dtype="float32", interpret=True)
        keys, kids = np.asarray(keys), np.asarray(kids)
        s = 1
        li = seg_list[s]
        ref = self._ref_keys(codes, cb, ids, norms, ctr, qv, li, s, "l2")
        for q in (0, 3):
            for lane in (0, 17, 127):
                cand = sorted(ref[q, lane::128])
                got = sorted([keys[s, q, lane], keys[s, q, 128 + lane]])
                np.testing.assert_allclose(got, cand[:2],
                                           rtol=1e-4, atol=1e-4)

    def test_ip_metric_keys(self):
        from raft_tpu.ops.pallas_kernels import ivfpq_lut_scan_topk

        rng = np.random.default_rng(13)
        n_lists, L, S, P, n_seg, seg = 3, 128, 8, 4, 3, 8
        codes, packed, cb, ids, norms, ctr, qv, seg_list = self._mk(
            rng, n_lists, L, S, 8, P, n_seg, seg, sizes=[L, 60, L])
        keys, kids = ivfpq_lut_scan_topk(
            jnp.asarray(seg_list), jnp.asarray(qv), jnp.asarray(packed),
            jnp.asarray(ids), jnp.asarray(norms), jnp.asarray(ctr),
            jnp.asarray(cb), "ip", pq_bits=8, pq_dim=S, L=L,
            lut_dtype="float32", interpret=True)
        keys, kids = np.asarray(keys), np.asarray(kids)
        s, q = 1, 2
        li = seg_list[s]
        ref = self._ref_keys(codes, cb, ids, norms, ctr, qv, li, s, "ip")
        got = {int(i): k for i, k in zip(kids[s, q], keys[s, q]) if i >= 0}
        want = {int(ids[li, l]): ref[q, l]
                for l in range(L) if ids[li, l] >= 0}
        assert set(got) == set(want)
        for i in want:
            np.testing.assert_allclose(got[i], want[i], rtol=1e-4,
                                       atol=1e-4)

    def test_lut_dtype_tolerance_tiers(self):
        """bf16 keys track f32 keys loosely; fp8 more loosely — the
        quantization ladder the lut_dtype knob buys."""
        from raft_tpu.ops.pallas_kernels import ivfpq_lut_scan_topk

        rng = np.random.default_rng(17)
        n_lists, L, S, P, n_seg, seg = 2, 128, 16, 2, 2, 8
        codes, packed, cb, ids, norms, ctr, qv, seg_list = self._mk(
            rng, n_lists, L, S, 8, P, n_seg, seg)
        outs = {}
        for dt in ("float32", "bfloat16", "float8_e4m3"):
            k_, _ = ivfpq_lut_scan_topk(
                jnp.asarray(seg_list), jnp.asarray(qv),
                jnp.asarray(packed), jnp.asarray(ids), jnp.asarray(norms),
                jnp.asarray(ctr), jnp.asarray(cb), "l2", pq_bits=8,
                pq_dim=S, L=L, lut_dtype=dt, interpret=True)
            outs[dt] = np.asarray(k_)
        fin = np.isfinite(outs["float32"])
        assert (np.isfinite(outs["bfloat16"]) == fin).all()
        scale = np.abs(outs["float32"][fin]).max()
        bf16_err = np.abs(outs["bfloat16"][fin]
                          - outs["float32"][fin]).max() / scale
        fp8_err = np.abs(outs["float8_e4m3"][fin]
                         - outs["float32"][fin]).max() / scale
        assert bf16_err < 0.05, bf16_err
        assert fp8_err < 0.30, fp8_err
        assert bf16_err <= fp8_err

    def test_dispatch_heuristic(self, monkeypatch):
        from raft_tpu.ops.pallas_kernels import pallas_lut_scan_wanted

        monkeypatch.delenv("RAFT_TPU_PALLAS_LUTSCAN", raising=False)
        # off-TPU, no force → not wanted
        assert not pallas_lut_scan_wanted(64, 256, 2, 64, 64, 1024, 128)
        monkeypatch.setenv("RAFT_TPU_PALLAS_LUTSCAN", "always")
        assert pallas_lut_scan_wanted(64, 256, 2, 64, 64, 1024, 128)
        # folded deep-100m shape: nb=64 inside 128-byte rows (G=2)
        assert pallas_lut_scan_wanted(64, 256, 2, 64, 128, 18312, 128)
        # a filter adds its byte stream + unpack operands to the VMEM
        # model without disqualifying the workhorse shapes
        assert pallas_lut_scan_wanted(64, 256, 2, 64, 64, 1024, 128,
                                      filtered=True)
        assert pallas_lut_scan_wanted(64, 256, 2, 64, 128, 18312, 128,
                                      filtered=True)
        # byte width not dividing the stored row width → unsupported
        assert not pallas_lut_scan_wanted(96, 256, 1, 96, 128, 1024, 96)
        # fold group too deep (G=16)
        assert not pallas_lut_scan_wanted(8, 256, 2, 8, 128, 1024, 16)
        monkeypatch.setenv("RAFT_TPU_PALLAS_LUTSCAN", "never")
        assert not pallas_lut_scan_wanted(64, 256, 2, 64, 64, 1024, 128)

    def _filter_bytes(self, ids, keep_global):
        """Packed per-list filter bytes over a [n_lists, L] GLOBAL id
        table — the host-side operand prep the dispatchers run
        (sample_filter.list_filter_bytes), built here via the same
        public helpers the tier uses."""
        from raft_tpu.core import bitset
        from raft_tpu.neighbors import sample_filter

        bits = bitset.from_mask(jnp.asarray(keep_global))
        return np.asarray(sample_filter.list_filter_bytes(
            bits, jnp.asarray(ids)))

    def _filtered_want(self, codes, cb, ids, norms, ctr, qv, li, s,
                       keep_global, L):
        ref = self._ref_keys(codes, cb, ids, norms, ctr, qv, li, s, "l2")
        return ref, {int(ids[li, l]): ref[:, l] for l in range(L)
                     if ids[li, l] >= 0 and keep_global[ids[li, l]]}

    @pytest.mark.parametrize("sel", [0.01, 0.1, 0.5])
    def test_filtered_parity_selectivity(self, sel):
        """Streamed filter mask: at every selectivity the emitted
        candidate set is exactly the KEPT subset of the unfiltered
        lossless set (L ≤ bins), keys exact, filtered ids absent."""
        from raft_tpu.ops.pallas_kernels import ivfpq_lut_scan_topk

        rng = np.random.default_rng(int(sel * 1000) + 29)
        n_lists, L, S, P, n_seg, seg = 4, 256, 16, 2, 5, 8
        codes, packed, cb, ids, norms, ctr, qv, seg_list = self._mk(
            rng, n_lists, L, S, 8, P, n_seg, seg,
            sizes=[L, L - 37, 3, 0])
        keep = rng.random(n_lists * L) < sel
        fbytes = self._filter_bytes(ids, keep)
        keys, kids = ivfpq_lut_scan_topk(
            jnp.asarray(seg_list), jnp.asarray(qv), jnp.asarray(packed),
            jnp.asarray(ids), jnp.asarray(norms), jnp.asarray(ctr),
            jnp.asarray(cb), "l2", pq_bits=8, pq_dim=S, L=L,
            lut_dtype="float32", filter_bytes=jnp.asarray(fbytes),
            interpret=True)
        keys, kids = np.asarray(keys), np.asarray(kids)
        for s in range(n_seg):
            li = seg_list[s]
            ref, want_by_id = self._filtered_want(
                codes, cb, ids, norms, ctr, qv, li, s, keep, L)
            for q in (0, seg - 1):
                got = {int(i): k for i, k in zip(kids[s, q], keys[s, q])
                       if i >= 0}
                assert set(got) == set(want_by_id), (s, q, sel)
                for i, kv in got.items():
                    np.testing.assert_allclose(kv, want_by_id[i][q],
                                               rtol=1e-4, atol=1e-4)

    def test_filtered_edge_masks(self):
        """all-pass == unfiltered bit-for-bit; all-fail emits only
        sentinels; a single survivor is found wherever it hides."""
        from raft_tpu.ops.pallas_kernels import ivfpq_lut_scan_topk

        rng = np.random.default_rng(31)
        n_lists, L, S, P, n_seg, seg = 3, 256, 16, 2, 4, 8
        codes, packed, cb, ids, norms, ctr, qv, seg_list = self._mk(
            rng, n_lists, L, S, 4, P, n_seg, seg, sizes=[L, 100, 17])

        def run(fbytes):
            k_, i_ = ivfpq_lut_scan_topk(
                jnp.asarray(seg_list), jnp.asarray(qv),
                jnp.asarray(packed), jnp.asarray(ids),
                jnp.asarray(norms), jnp.asarray(ctr), jnp.asarray(cb),
                "l2", pq_bits=4, pq_dim=S, L=L, lut_dtype="float32",
                filter_bytes=(None if fbytes is None
                              else jnp.asarray(fbytes)),
                interpret=True)
            return np.asarray(k_), np.asarray(i_)

        base_k, base_i = run(None)
        # all-pass: identical to no filter
        allpass = np.ones(n_lists * L, bool)
        k1, i1 = run(self._filter_bytes(ids, allpass))
        np.testing.assert_array_equal(i1, base_i)
        np.testing.assert_allclose(k1, base_k, rtol=0, atol=0)
        # all-fail: nothing but sentinels
        k0, i0 = run(self._filter_bytes(ids, np.zeros(n_lists * L, bool)))
        assert (i0 == -1).all()
        assert not np.isfinite(k0).any()
        # single survivor: exactly that id, everywhere its list is probed
        surv = np.zeros(n_lists * L, bool)
        li0 = int(seg_list[1])
        lane = int(np.where(ids[li0] >= 0)[0].max())  # last valid slot
        gid = int(ids[li0, lane])
        assert gid >= 0
        surv[gid] = True
        ks, is_ = run(self._filter_bytes(ids, surv))
        for s in range(n_seg):
            got = set(int(i) for i in is_[s].ravel() if i >= 0)
            assert got == ({gid} if int(seg_list[s]) == li0 else set()), s

    def test_filtered_ragged_tail_and_folded(self):
        """Filter bytes pad to whole code tiles with 0 (= filtered): a
        ragged list tail plus lane-folded storage must not admit any
        OOB candidate, and kept candidates survive exactly."""
        from raft_tpu.ops.pallas_kernels import ivfpq_lut_scan_topk

        rng = np.random.default_rng(37)
        n_lists, L, S, P, n_seg, seg = 3, 240, 16, 2, 4, 8
        codes, packed, cb, ids, norms, ctr, qv, seg_list = self._mk(
            rng, n_lists, L, S, 8, P, n_seg, seg,
            sizes=[L, 100, 17], fold=True)
        keep = rng.random(n_lists * L) < 0.4
        fbytes = self._filter_bytes(ids, keep)
        keys, kids = ivfpq_lut_scan_topk(
            jnp.asarray(seg_list), jnp.asarray(qv), jnp.asarray(packed),
            jnp.asarray(ids), jnp.asarray(norms), jnp.asarray(ctr),
            jnp.asarray(cb), "l2", pq_bits=8, pq_dim=S, L=L,
            lut_dtype="float32", filter_bytes=jnp.asarray(fbytes),
            interpret=True)
        keys, kids = np.asarray(keys), np.asarray(kids)
        for s in range(n_seg):
            li = seg_list[s]
            ref, want_by_id = self._filtered_want(
                codes, cb, ids, norms, ctr, qv, li, s, keep, L)
            for q in (0, seg - 1):
                got = {int(i): k for i, k in zip(kids[s, q], keys[s, q])
                       if i >= 0}
                assert set(got) == set(want_by_id), (s, q)
                for i, kv in got.items():
                    np.testing.assert_allclose(kv, want_by_id[i][q],
                                               rtol=1e-4, atol=1e-4)


class TestGatherRefine:
    """gather_refine_topk (interpret mode off-TPU) vs numpy reference:
    streamed candidate-row gather + exact metric epilogue + running
    top-k, with no [m, C, d] buffer (ISSUE 4 acceptance)."""

    def _ref(self, data, q, cand, metric):
        rows = data[np.clip(cand, 0, data.shape[0] - 1)].astype(np.float32)
        s = np.einsum("md,mcd->mc", q, rows)
        if metric == "ip":
            key = -s
        elif metric == "cos":
            qn = np.sqrt(np.maximum((q * q).sum(1), 1e-30))
            cn = np.sqrt(np.maximum((rows ** 2).sum(-1), 1e-30))
            key = 1.0 - s / (qn[:, None] * cn)
        else:
            key = np.maximum((q * q).sum(1)[:, None]
                             + (rows ** 2).sum(-1) - 2.0 * s, 0.0)
        return np.where(cand >= 0, key, np.inf)

    def _check(self, data, q, cand, k, metric, **kw):
        from raft_tpu.ops import gather_refine_topk

        keys, ids = gather_refine_topk(jnp.asarray(data), jnp.asarray(q),
                                       jnp.asarray(cand), k, metric,
                                       interpret=True)
        keys, ids = np.asarray(keys), np.asarray(ids)
        ref = self._ref(np.asarray(data, np.float32), q, cand, metric)
        order = np.argsort(ref, axis=1, kind="stable")[:, :k]
        want_v = np.take_along_axis(ref, order, 1)
        np.testing.assert_allclose(keys, want_v, **kw)
        want_i = np.where(np.isinf(want_v), -1,
                          np.take_along_axis(cand, order, 1))
        # ids must agree wherever keys are strictly ordered (ties may
        # legally reorder between the buffer merge and a full argsort)
        strict = np.ones_like(keys, dtype=bool)
        strict[:, 1:] &= want_v[:, 1:] != want_v[:, :-1]
        strict[:, :-1] &= want_v[:, :-1] != want_v[:, 1:]
        np.testing.assert_array_equal(ids[strict], want_i[strict])

    def test_metrics_match_numpy(self, rng):
        data = rng.standard_normal((700, 96)).astype(np.float32)
        q = rng.standard_normal((21, 96)).astype(np.float32)
        cand = rng.integers(0, 700, (21, 300)).astype(np.int32)
        for metric in ("l2", "ip", "cos"):
            self._check(data, q, cand, 10, metric, rtol=1e-4, atol=1e-4)

    def test_invalid_and_ragged(self, rng):
        data = rng.standard_normal((500, 40)).astype(np.float32)
        q = rng.standard_normal((9, 40)).astype(np.float32)
        cand = rng.integers(0, 500, (9, 270)).astype(np.int32)
        cand[0, :] = -1            # fully invalid row
        cand[1, -31:] = -1         # ragged tail
        cand[2, 10:30] = cand[2, 9]  # duplicates
        self._check(data, q, cand, 8, "l2", rtol=1e-4, atol=1e-4)

    def test_bf16_recon_rows(self, rng):
        """bf16 dataset rows (the recon-cache input) stream through the
        row DMAs dtype-preserved; keys computed in f32 against the
        bf16-quantized values."""
        data = rng.standard_normal((400, 64)).astype(np.float32)
        data_bf = jnp.asarray(data).astype(jnp.bfloat16)
        q = rng.standard_normal((10, 64)).astype(np.float32)
        cand = rng.integers(0, 400, (10, 256)).astype(np.int32)
        self._check(np.asarray(data_bf.astype(jnp.float32)), q, cand, 8,
                    "l2", rtol=1e-4, atol=1e-4)

    def test_short_rows_pad_with_invalid(self, rng):
        from raft_tpu.ops import gather_refine_topk

        data = rng.standard_normal((100, 16)).astype(np.float32)
        q = rng.standard_normal((3, 16)).astype(np.float32)
        cand = np.full((3, 200), -1, np.int32)
        cand[:, :4] = rng.integers(0, 100, (3, 4))
        keys, ids = gather_refine_topk(jnp.asarray(data), jnp.asarray(q),
                                       jnp.asarray(cand), 10, "l2",
                                       interpret=True)
        keys, ids = np.asarray(keys), np.asarray(ids)
        assert np.isfinite(keys[:, :4]).all()
        assert np.isinf(keys[:, 4:]).all() and (ids[:, 4:] == -1).all()

    def test_k_over_merge_budget_raises(self, rng):
        from raft_tpu.ops import gather_refine_topk
        from raft_tpu.ops.pallas_kernels import GATHER_REFINE_MAX_K

        with pytest.raises(ValueError):
            gather_refine_topk(jnp.zeros((10, 16)), jnp.zeros((2, 16)),
                               jnp.zeros((2, 300), jnp.int32),
                               GATHER_REFINE_MAX_K + 1, "l2",
                               interpret=True)

    def test_dispatch_heuristic(self, monkeypatch):
        from raft_tpu.ops.pallas_kernels import pallas_gather_refine_wanted

        monkeypatch.delenv("RAFT_TPU_PALLAS_REFINE", raising=False)
        # off-TPU, no force → not wanted
        assert not pallas_gather_refine_wanted(10_000, 2000, 96, 10)
        monkeypatch.setenv("RAFT_TPU_PALLAS_REFINE", "always")
        assert pallas_gather_refine_wanted(10_000, 2000, 96, 10)
        # a filter adds the per-candidate word scratch without
        # disqualifying the acceptance shape
        assert pallas_gather_refine_wanted(10_000, 2000, 96, 10,
                                           filtered=True)
        # k past the merge budget / tiny candidate sets stay on XLA
        assert not pallas_gather_refine_wanted(10_000, 2000, 96, 65)
        assert not pallas_gather_refine_wanted(10_000, 100, 96, 10)
        monkeypatch.setenv("RAFT_TPU_PALLAS_REFINE", "never")
        assert not pallas_gather_refine_wanted(10_000, 2000, 96, 10)

    def _check_filtered(self, data, q, cand, k, metric, keep, **kw):
        from raft_tpu.core import bitset
        from raft_tpu.ops import gather_refine_topk

        bits = bitset.from_mask(jnp.asarray(keep))
        keys, ids = gather_refine_topk(jnp.asarray(data), jnp.asarray(q),
                                       jnp.asarray(cand), k, metric,
                                       filter_bits=bits, interpret=True)
        keys, ids = np.asarray(keys), np.asarray(ids)
        ref = self._ref(np.asarray(data, np.float32), q, cand, metric)
        # the filter joins the invalid-id mask: cleared bits → +inf/-1
        kept = (cand >= 0) & keep[np.clip(cand, 0, len(keep) - 1)]
        ref = np.where(kept, ref, np.inf)
        order = np.argsort(ref, axis=1, kind="stable")[:, :k]
        want_v = np.take_along_axis(ref, order, 1)
        np.testing.assert_allclose(keys, want_v, **kw)
        want_i = np.where(np.isinf(want_v), -1,
                          np.take_along_axis(cand, order, 1))
        strict = np.ones_like(keys, dtype=bool)
        strict[:, 1:] &= want_v[:, 1:] != want_v[:, :-1]
        strict[:, :-1] &= want_v[:, :-1] != want_v[:, 1:]
        np.testing.assert_array_equal(ids[strict], want_i[strict])
        got = ids[ids >= 0]
        assert keep[got].all() if got.size else True

    def test_filtered_metrics_match_numpy(self, rng):
        """Per-candidate bitset-word fetch through the row-DMA queue:
        cleared bits poison rows to +inf/-1 across every metric."""
        data = rng.standard_normal((700, 96)).astype(np.float32)
        q = rng.standard_normal((21, 96)).astype(np.float32)
        cand = rng.integers(0, 700, (21, 300)).astype(np.int32)
        keep = rng.random(700) < 0.5
        for metric in ("l2", "ip", "cos"):
            self._check_filtered(data, q, cand, 10, metric, keep,
                                 rtol=1e-4, atol=1e-4)

    def test_filtered_edge_masks(self, rng):
        """all-pass == unfiltered; all-fail → all sentinels; a single
        surviving candidate is returned alone; ragged/invalid tails
        compose with the filter."""
        from raft_tpu.core import bitset
        from raft_tpu.ops import gather_refine_topk

        data = rng.standard_normal((500, 40)).astype(np.float32)
        q = rng.standard_normal((9, 40)).astype(np.float32)
        cand = rng.integers(0, 500, (9, 270)).astype(np.int32)
        cand[1, -31:] = -1         # ragged tail composes with the filter

        base_k, base_i = gather_refine_topk(
            jnp.asarray(data), jnp.asarray(q), jnp.asarray(cand), 8,
            "l2", interpret=True)
        allpass = bitset.from_mask(jnp.ones(500, bool))
        k1, i1 = gather_refine_topk(
            jnp.asarray(data), jnp.asarray(q), jnp.asarray(cand), 8,
            "l2", filter_bits=allpass, interpret=True)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(base_i))
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(base_k))

        allfail = bitset.from_mask(jnp.zeros(500, bool))
        k0, i0 = gather_refine_topk(
            jnp.asarray(data), jnp.asarray(q), jnp.asarray(cand), 8,
            "l2", filter_bits=allfail, interpret=True)
        assert (np.asarray(i0) == -1).all()
        assert np.isinf(np.asarray(k0)).all()

        surv = np.zeros(500, bool)
        gid = int(cand[4, 100])
        surv[gid] = True
        ks, is_ = gather_refine_topk(
            jnp.asarray(data), jnp.asarray(q), jnp.asarray(cand), 8,
            "l2", filter_bits=bitset.from_mask(jnp.asarray(surv)),
            interpret=True)
        is_ = np.asarray(is_)
        for m in range(9):
            got = set(is_[m][is_[m] >= 0].tolist())
            want = {gid} if gid in set(cand[m].tolist()) else set()
            assert got == want, m
