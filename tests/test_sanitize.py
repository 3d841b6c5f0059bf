"""Sanitizer-mode coverage: bitset + sample_filter under the runtime
guards (ISSUE 3 satellite — the transfer guard exposed host round-trips
in the ``set_bits`` paths, fixed by jitting the packing ops), plus the
jit-cache-miss budget contract on a search hot path.

Every test here passes in the normal tier-1 lane too — the guards are
scoped explicitly via :mod:`raft_tpu.obs.sanitize`; only the
``recompile_budget`` markers need the ``RAFT_TPU_SANITIZE=1`` lane (the
conftest fixture enforces them there and ignores them elsewhere).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core import bitset
from raft_tpu.neighbors import brute_force, sample_filter
from raft_tpu.obs import sanitize


def _rank_promotion_raise():
    """Context: jax_numpy_rank_promotion='raise' (restores prior value)."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        prev = jax.config.jax_numpy_rank_promotion
        jax.config.update("jax_numpy_rank_promotion", "raise")
        try:
            yield
        finally:
            jax.config.update("jax_numpy_rank_promotion", prev)

    return ctx()


class TestBitsetSanitized:
    def test_roundtrip_under_guard_and_rank_raise(self, rng):
        mask_h = rng.random(301) < 0.5
        mask = jnp.asarray(mask_h)
        with _rank_promotion_raise():
            bits = bitset.from_mask(mask)
            with sanitize.no_host_transfers():
                back = bitset.to_mask(bits, 301)
                cnt = bitset.count(bits, 301)
                flipped = bitset.flip(bits)
                jax.block_until_ready((back, cnt, flipped))
        np.testing.assert_array_equal(np.asarray(back), mask_h)
        assert int(cnt) == int(mask_h.sum())
        np.testing.assert_array_equal(
            np.asarray(bitset.to_mask(flipped, 301)), ~mask_h)

    def test_set_bits_word_collisions_under_guard(self):
        # several indices landing in the same uint32 word — the
        # segment-reduction path must keep every write
        idx = jnp.asarray([0, 1, 31, 32, 33, 64, 95, 99])
        idx3 = jnp.asarray([0, 1, 31])  # device-resident BEFORE the guard
        bits0 = bitset.create(100, default_value=False)
        with _rank_promotion_raise(), sanitize.no_host_transfers():
            bits = bitset.set_bits(bits0, idx, True)
            cleared = bitset.set_bits(bits, idx3, False)
            jax.block_until_ready((bits, cleared))
        expect = np.zeros(100, bool)
        expect[np.asarray(idx)] = True
        np.testing.assert_array_equal(np.asarray(bitset.to_mask(bits, 100)),
                                      expect)
        expect[np.asarray(idx[:3])] = False
        np.testing.assert_array_equal(
            np.asarray(bitset.to_mask(cleared, 100)), expect)

    def test_test_and_passes_under_guard(self):
        remove = np.asarray([2, 7, 40])
        bits = sample_filter.make_filter(64, remove=remove)
        ids = jnp.asarray([[0, 2, 63], [7, -1, 40]])
        probe = jnp.asarray([2, 3, 40])
        with _rank_promotion_raise(), sanitize.no_host_transfers():
            ok = sample_filter.passes(bits, ids)
            t = bitset.test(bits, probe)
            none_ok = sample_filter.passes(None, ids)
            jax.block_until_ready((ok, t, none_ok))
        np.testing.assert_array_equal(
            np.asarray(ok), [[True, False, True], [False, False, False]])
        np.testing.assert_array_equal(np.asarray(t), [False, True, False])
        # None filter is the allow-all shortcut: pads included (callers
        # mask padding separately — this is the established contract)
        np.testing.assert_array_equal(np.asarray(none_ok),
                                      np.ones((2, 3), bool))

    def test_make_filter_keep_semantics(self):
        keep = np.asarray([1, 5, 9])
        bits = sample_filter.make_filter(32, keep=keep)
        mask = np.asarray(bitset.to_mask(bits, 32))
        expect = np.zeros(32, bool)
        expect[keep] = True
        np.testing.assert_array_equal(mask, expect)
        with pytest.raises(ValueError):
            sample_filter.make_filter(8, remove=[1], keep=[2])


@pytest.fixture(scope="module")
def warm_filtered_knn(request):
    """Build + warm a filtered brute-force search so the steady-state
    test below measures a hot jit cache (module-scope: the warmup
    compiles land OUTSIDE the function-scoped budget fixture)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.random((500, 32), dtype=np.float32))
    q = jnp.asarray(rng.random((16, 32), dtype=np.float32))
    index = brute_force.build(x)
    fbits = sample_filter.make_filter(500, remove=np.arange(0, 500, 7))
    jax.block_until_ready(brute_force.knn(index, q, 10, fbits))
    return index, q, fbits


@pytest.mark.recompile_budget(0)
def test_filtered_knn_steady_state(warm_filtered_knn):
    """The serving contract on a hot path: a warm, same-shape filtered
    search triggers ZERO backend compiles and ZERO implicit host
    transfers. In RAFT_TPU_SANITIZE=1 mode the budget marker turns any
    retrace into a failure."""
    index, q, fbits = warm_filtered_knn
    with sanitize.no_host_transfers():
        d, i = brute_force.knn(index, q, 10, fbits)
        jax.block_until_ready((d, i))
    ids = np.asarray(i)
    # filtered rows (multiples of 7) must never be returned
    assert not np.isin(ids, np.arange(0, 500, 7)).any()
    assert ids.shape == (16, 10)


class TestCollectiveSchedule:
    """The runtime half of the SPMD correctness pass (graftlint
    GL06–GL10): per traced program, every device's collective schedule
    must be identical — a collective gated on ``axis_index`` deadlocks
    (or silently zero-fills) a real mesh while single-device tests stay
    green. Runs on the 8-device CPU mesh."""

    @pytest.fixture(scope="class")
    def mesh(self):
        from raft_tpu.parallel import make_mesh

        return make_mesh(axis_names=("shard",))

    def test_axis_gated_psum_is_caught(self, mesh):
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        def prog(x):
            def local(v):
                rank = lax.axis_index("shard")
                return lax.cond(rank == 0,
                                lambda u: lax.psum(u, "shard"),
                                lambda u: u, v)
            return shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P("shard"), check_vma=False)(x)

        with pytest.raises(sanitize.CollectiveScheduleDivergence) as e:
            sanitize.assert_uniform_collective_schedule(
                prog, jnp.ones((8, 4), jnp.float32))
        assert "diverges" in str(e.value)

    def test_uniform_branches_pass(self, mesh):
        # both branches committing to the SAME schedule is safe: every
        # device executes a psum regardless of the predicate
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        def prog(x):
            def local(v):
                rank = lax.axis_index("shard")
                return lax.cond(rank == 0,
                                lambda u: lax.psum(u, "shard"),
                                lambda u: lax.psum(u * 2.0, "shard"), v)
            return shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                             out_specs=P("shard"), check_vma=False)(x)

        sched = sanitize.collective_schedule(
            prog, jnp.ones((8, 4), jnp.float32))
        assert [e[0] for e in sched] == ["psum"]

    def test_comms_schedule_recorder(self, mesh):
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from raft_tpu.parallel import Comms

        comms = Comms("shard")

        def body(v):
            return comms.send_recv_ring(comms.allreduce(v))

        fn = shard_map(body, mesh=mesh, in_specs=(P("shard"),),
                       out_specs=P("shard"), check_vma=False)
        with sanitize.record_comms_schedule() as rec:
            jax.block_until_ready(jax.jit(fn)(jnp.ones((8,))))
        assert [(v, a) for v, a, _ in rec] == \
            [("allreduce", "shard"), ("send_recv_ring", "shard")]
        assert all(b > 0 for _, _, b in rec)
        # recording is scoped: outside the context nothing records
        assert not sanitize.comms_schedule_recording()


class TestScopedX64:
    """The capacity prover's x64 scoping (PR-10 satellite): proofs
    trace int64 id paths, but ``jax_enable_x64`` is process-global and
    silently changes every later test's dtypes — the scope must
    save/restore, including on exceptions."""

    def test_scope_enables_and_restores(self):
        assert not jax.config.jax_enable_x64  # conftest pins it off
        with sanitize.scoped_x64(True):
            assert jax.config.jax_enable_x64
            assert jnp.arange(3, dtype=jnp.int64).dtype == jnp.int64
        assert not jax.config.jax_enable_x64
        assert jnp.asarray([1]).dtype == jnp.int32

    def test_scope_restores_on_exception(self):
        with pytest.raises(RuntimeError):
            with sanitize.scoped_x64(True):
                raise RuntimeError("boom")
        assert not jax.config.jax_enable_x64

    def test_prover_never_leaks_x64(self):
        """A full capacity proof (which traces int64 ids under the
        scope) leaves the process exactly as it found it — whether the
        proof passes or raises."""
        import tools.capacity_prove as cp

        cp.prove_ivf_flat()
        assert not jax.config.jax_enable_x64
        with pytest.raises(sanitize.CapacityError):
            sanitize.assert_billion_safe(
                lambda q: jnp.arange(cp.DEFAULT_N, dtype=jnp.int32)[:2] + q,
                jax.ShapeDtypeStruct((2,), jnp.int32), what="seeded")
        assert not jax.config.jax_enable_x64
        assert jnp.asarray([1]).dtype == jnp.int32


def test_recompile_budget_fires():
    """The budget context itself: a fresh shape inside a 0-budget scope
    must raise RecompileBudgetExceeded."""
    sanitize.install_compile_counter()

    @jax.jit
    def f(v):
        return v * 2.0 + 1.0

    with pytest.raises(sanitize.RecompileBudgetExceeded):
        with sanitize.recompile_budget(0, what="fresh shape"):
            jax.block_until_ready(f(jnp.arange(173, dtype=jnp.float32)))
    # warm now → budget 0 holds
    with sanitize.recompile_budget(0, what="warm shape"):
        jax.block_until_ready(f(jnp.arange(173, dtype=jnp.float32)))
