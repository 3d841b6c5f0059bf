"""graftlint self-test: every rule fires on a known-bad snippet, every
suppression form silences exactly what it claims, and the real tree
stays clean (the CI gate's contract — ci/test_python.sh runs
``python -m tools.graftlint raft_tpu`` as a blocking step).

Pure stdlib under test — no jax import needed; snippets are linted as
source strings.
"""

import json
import os
import subprocess
import sys

import pytest

from tools import graftlint


def rules_of(findings):
    return sorted({f.rule for f in findings})


def lint(src, path="raft_tpu/neighbors/fake.py", select=None):
    return graftlint.lint_source(src, path=path, select=select)


# ---------------------------------------------------------------------------
# GL01 — host syncs in hot bodies
# ---------------------------------------------------------------------------

GL01_BAD = """
import jax
import numpy as np

@jax.jit
def f(x):
    v = x.item()
    h = np.asarray(x)
    jax.device_get(x)
    x.block_until_ready()
    s = float(x)
    return v, h, s
"""


def test_gl01_fires_on_every_sync_kind():
    findings = [f for f in lint(GL01_BAD) if f.rule == "GL01"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 5
    for needle in (".item()", "np.asarray", "jax.device_get",
                   ".block_until_ready()", "float(x)"):
        assert needle in msgs


def test_gl01_traced_and_kernel_contexts():
    src = """
from raft_tpu.core.tracing import traced

@traced("raft_tpu.x")
def entry(x):
    return x.item()

def scan_kernel(a_ref, b_ref, o_ref):
    v = float(a_ref)
    o_ref[:] = v
"""
    findings = [f for f in lint(src) if f.rule == "GL01"]
    assert len(findings) == 2
    assert any("@traced function" in f.message for f in findings)
    assert any("Pallas kernel" in f.message for f in findings)


def test_gl01_quiet_on_eager_helpers():
    src = """
import numpy as np

def host_helper(x):
    return np.asarray(x).item()
"""
    assert not [f for f in lint(src) if f.rule == "GL01"]


# ---------------------------------------------------------------------------
# GL02 — raw env flag parsing
# ---------------------------------------------------------------------------

def test_gl02_fires_on_flag_vocab_compare():
    src = """
import os

def wanted():
    force = os.environ.get("RAFT_TPU_X", "auto")
    if force == "never":
        return False
    return force == "always"
"""
    assert rules_of(lint(src)) == ["GL02"]


def test_gl02_fires_on_inline_truth_test_and_chain():
    src = """
import os

def a():
    if os.environ.get("X"):
        return 1

def b():
    return os.environ.get("Y", "").strip().lower() not in ("", "0", "no")
"""
    findings = [f for f in lint(src) if f.rule == "GL02"]
    assert len(findings) == 2


def test_gl02_quiet_on_value_reads():
    src = """
import os

def paths():
    jsonl = os.environ.get("RAFT_TPU_BENCH_OBS_JSONL")
    if jsonl:
        open(jsonl)
    n = int(os.environ.get("RAFT_TPU_BENCH_N", 1000))
    return n
"""
    assert not [f for f in lint(src) if f.rule == "GL02"]


# ---------------------------------------------------------------------------
# GL03 — recompile hazards
# ---------------------------------------------------------------------------

def test_gl03_fires_on_tracer_branch_and_unhashable_static():
    src = """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("k",))
def f(x, k, opts=[1, 2]):
    if x > 0:
        return x
    return -x

@functools.partial(jax.jit, static_argnames=("opts",))
def g(x, opts=[1, 2]):
    return x
"""
    findings = [f for f in lint(src) if f.rule == "GL03"]
    assert len(findings) == 2
    assert any("traced value" in f.message for f in findings)
    assert any("unhashable" in f.message for f in findings)


def test_gl03_quiet_on_static_and_structure_branches():
    src = """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def f(x, mask, k, interpret=False):
    if interpret:
        k = k + 1
    if mask is not None:
        x = x * 1.0
    if x.ndim == 2 and k > x.shape[0]:
        return x
    return x
"""
    assert not [f for f in lint(src) if f.rule == "GL03"]


# ---------------------------------------------------------------------------
# GL04 — observability contract on public entry points
# ---------------------------------------------------------------------------

GL04_BAD = """
def build(dataset):
    return dataset

def search(index, q, k):
    return index
"""

GL04_GOOD = """
from raft_tpu.core.tracing import traced, span

@traced("raft_tpu.fake.build")
def build(dataset):
    return dataset

def search(index, q, k):
    with span("scan"):
        return index

def _private_helper(x):
    return x

def not_an_entry_verb(x):
    return x
"""


def test_gl04_fires_only_in_entry_packages():
    assert len([f for f in lint(GL04_BAD) if f.rule == "GL04"]) == 2
    # same source outside neighbors/cluster/distance: no contract
    assert not lint(GL04_BAD, path="raft_tpu/sparse/fake.py")


def test_gl04_satisfied_by_traced_or_span():
    assert not [f for f in lint(GL04_GOOD) if f.rule == "GL04"]


# ---------------------------------------------------------------------------
# GL05 — Pallas kernel constraints
# ---------------------------------------------------------------------------

GL05_BAD = """
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128

def bad_kernel(x_ref, idx_ref, o_ref):
    o_ref[:] = jnp.take(x_ref[:], idx_ref[:], axis=1)

def caller(x, idx):
    return pl.pallas_call(
        bad_kernel,
        in_specs=[
            pl.BlockSpec((8, 100), lambda i: (i, 0)),
            pl.BlockSpec(),
            pl.BlockSpec((8, _LANES), lambda i: (i, 0)),
        ],
    )(x, idx)
"""


def test_gl05_fires_on_lane_tiling_memory_space_and_gather():
    findings = [f for f in lint(GL05_BAD) if f.rule == "GL05"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 3
    assert "not a multiple of 128" in msgs
    assert "memory_space" in msgs
    assert "lane-axis gather" in msgs
    # const-resolved _LANES block and the SMEM spec are fine
    src_ok = GL05_BAD.replace("(8, 100)", "(8, 256)") \
                     .replace("pl.BlockSpec(),",
                              "pl.BlockSpec(memory_space='smem'),") \
                     .replace("jnp.take(x_ref[:], idx_ref[:], axis=1)",
                              "x_ref[:]")
    assert not [f for f in lint(src_ok) if f.rule == "GL05"]


# ---------------------------------------------------------------------------
# GL06 — collective scope / axis consistency
# ---------------------------------------------------------------------------

GL06_BAD = """
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from raft_tpu.parallel.comms import Comms


def merge(x):
    comms = Comms("shards")          # typo: the mesh binds "shard"
    return comms.allreduce(x)


def helper(x, axis="shard"):
    return lax.psum(x, axis)         # right axis, never shard_mapped


def run(x, mesh, axis="shard"):
    fn = shard_map(lambda v: v, mesh=mesh, in_specs=(P(axis),),
                   out_specs=P(axis), check_vma=False)
    return fn(x)
"""

GL06_GOOD = """
from jax.sharding import PartitionSpec as P
from jax import shard_map
from raft_tpu.parallel.comms import Comms


def merge(vals, axis):
    comms = Comms(axis)              # axis-generic helper: undecidable
    return comms.allgather(vals)


def run(x, mesh, axis="shard"):
    comms = Comms(axis)

    def local(v):
        return comms.allreduce(merge(v, axis))

    fn = shard_map(local, mesh=mesh, in_specs=(P(axis),),
                   out_specs=P(), check_vma=False)
    return fn(x)
"""


def test_gl06_fires_on_unbound_axis_and_unwrapped_collective():
    findings = [f for f in lint(GL06_BAD) if f.rule == "GL06"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 3
    assert "not bound by any mesh/axis declaration" in msgs
    assert "never wrapped in (or called from) shard_map" in msgs


def test_gl06_quiet_on_wrapped_and_axis_generic_code():
    assert not [f for f in lint(GL06_GOOD) if f.rule == "GL06"]


GL06_TUPLE = """
from jax.sharding import PartitionSpec as P
from jax import shard_map
from raft_tpu.parallel.comms import Comms
from raft_tpu.parallel.mesh import hier_mesh

HIER_AXIS_NAMES = ("dcn", "ici")


def run(x, n_outer, n_inner):
    mesh = hier_mesh(n_inner, n_outer, axis_names=HIER_AXIS_NAMES)

    def local(v):
        inner = Comms("ici")
        outer = Comms("dcn")
        return outer.allgather(inner.allreduce(v))

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(("dcn", "ici"), None),),
                   out_specs=P(("dcn", "ici"), None), check_vma=False)
    return fn(x)
"""


def test_gl06_resolves_tuple_axis_consts():
    # the 2-D mesh idiom: axis names live in a module tuple constant
    # handed to the mesh constructor — both constituent axes are bound
    assert not [f for f in lint(GL06_TUPLE) if f.rule == "GL06"]
    typo = GL06_TUPLE.replace('Comms("ici")', 'Comms("icy")')
    findings = [f for f in lint(typo) if f.rule == "GL06"]
    assert len(findings) == 1
    assert "not bound" in findings[0].message


# ---------------------------------------------------------------------------
# GL07 — static ppermute perms
# ---------------------------------------------------------------------------

GL07_BAD = """
from jax import lax


def bad_src(x):
    return lax.ppermute(x, "shard", perm=[(0, 1), (0, 2), (1, 0)])


def bad_dup(x):
    return lax.ppermute(x, "shard", perm=[(0, 1), (1, 1), (2, 0)])


def bad_drop(x):
    # shift without wraparound: rank 0 silently receives zeros
    return lax.ppermute(x, "shard", perm=[(0, 1), (1, 2), (2, 3)])


def ring_exchange(x, comms):
    perm = [(0, 1), (1, 0), (2, 3), (3, 2)]  # two 2-cycles, no ring
    return comms.ppermute(x, perm)
"""

GL07_GOOD = """
from jax import lax


def ring_step(x, comms):
    return comms.ppermute(x, [(0, 1), (1, 2), (2, 3), (3, 0)])


def dynamic(x, comms, size):
    perm = [(i, (i + 1) % size) for i in range(size)]  # not static
    return comms.ppermute(x, perm)
"""


def test_gl07_fires_on_non_permutations_and_open_rings():
    findings = [f for f in lint(GL07_BAD) if f.rule == "GL07"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 4
    assert "duplicate source" in msgs
    assert "not injective" in msgs
    assert "ZERO-FILLS" in msgs
    assert "single cycle" in msgs


def test_gl07_quiet_on_closed_ring_and_dynamic_perms():
    assert not [f for f in lint(GL07_GOOD) if f.rule == "GL07"]


# ---------------------------------------------------------------------------
# GL08 — Pallas DMA lifetime
# ---------------------------------------------------------------------------

GL08_BAD = """
from jax.experimental.pallas import tpu as pltpu


def leak_kernel(hbm_ref, o_ref, sem):
    cp = pltpu.make_async_copy(hbm_ref, o_ref, sem)
    cp.start()
    o_ref[:] = o_ref[:] * 2.0


def race_kernel(hbm_ref, o_ref, sem):
    for i in range(4):
        cp = pltpu.make_async_copy(hbm_ref.at[i], o_ref, sem)
        cp.start()
    cp.wait()


def branch_kernel(hbm_ref, o_ref, sem, flag):
    cp = pltpu.make_async_copy(hbm_ref, o_ref, sem)
    cp.start()
    if flag:
        cp.wait()


def shared_sem_kernel(a_hbm, b_hbm, o_ref, sem):
    c1 = pltpu.make_async_copy(a_hbm, o_ref.at[0], sem)
    c2 = pltpu.make_async_copy(b_hbm, o_ref.at[1], sem)
    c1.start()
    c2.start()
    c1.wait()
    c2.wait()
"""

# the gather_refine factory/queue idiom (NBUF copies in flight, waits
# in the fori_loop body) must stay quiet
GL08_GOOD = """
import jax
from jax.experimental.pallas import tpu as pltpu


def good_kernel(ids_hbm, data_hbm, o_ref, ids_smem, rows, sem_ids, sems):
    cp = pltpu.make_async_copy(ids_hbm, ids_smem, sem_ids)
    cp.start()
    cp.wait()

    def row_copy(t):
        return pltpu.make_async_copy(
            data_hbm.at[t], rows.at[t], sems.at[t % 4])

    for t in range(4):
        row_copy(t).start()

    def stream(t, carry):
        row_copy(t).wait()
        return carry

    jax.lax.fori_loop(0, 16, stream, 0)
    o_ref[:] = rows[:]
"""


def test_gl08_fires_on_every_lifetime_violation():
    findings = [f for f in lint(GL08_BAD) if f.rule == "GL08"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 4
    assert "never waited" in msgs
    assert "restarted" in msgs
    assert "all control paths" in msgs
    assert "SAME semaphore" in msgs


def test_gl08_quiet_on_the_queue_idiom():
    assert not [f for f in lint(GL08_GOOD) if f.rule == "GL08"]


# the ISSUE 11 overlap-idiom extension: factory calls with statically
# stable arguments resolve to concrete semaphore slots

GL08_FACTORY_BAD = """
from jax.experimental.pallas import tpu as pltpu


def same_sem_kernel(hbm, out, sems):
    def cp(which):
        return pltpu.make_async_copy(hbm.at[which], out.at[which],
                                     sems.at[0])
    cp(0).start()
    cp(1).start()
    cp(0).wait()
    cp(1).wait()


def loop_restart_kernel(hbm, out, sems):
    def cp(i):
        return pltpu.make_async_copy(hbm.at[i], out.at[i], sems.at[i])
    for t in range(4):
        cp(0).start()
    cp(0).wait()


def exit_unwaited_kernel(hbm, out, sems):
    def cp(i):
        return pltpu.make_async_copy(hbm.at[i], out.at[i], sems.at[i])
    cp(0).start()
    cp(0).wait()
    cp(0).start()
"""

GL08_FACTORY_GOOD = """
from jax.experimental.pallas import tpu as pltpu


def overlap_kernel(hbm, out, sems):
    # two in-flight copies on DISTINCT semaphores: the legitimate
    # pipelined schedule (the ring kernel's overlap idiom)
    def cp(i):
        return pltpu.make_async_copy(hbm.at[i], out.at[i], sems.at[i])
    cp(0).start()
    cp(1).start()
    cp(0).wait()
    cp(1).wait()


def loop_carried_kernel(hbm, out, sems):
    # slot reuse across loop-carried hops, waited before restart
    def cp(i):
        return pltpu.make_async_copy(hbm.at[i], out.at[i], sems.at[i])
    cp(0).start()
    for s in range(4):
        cp(0).wait()
        cp(0).start()
    cp(0).wait()


def rotated_kernel(hbm, out, sems):
    # dynamically-rotated slots (loop-varying args) defer to the
    # whole-tree tally — the gather-refine prologue-fill idiom
    def cp(t):
        return pltpu.make_async_copy(hbm.at[t], out.at[t],
                                     sems.at[t % 2])
    cp(0).start()
    for t in range(1, 8):
        cp(t).start()
        cp(t - 1).wait()
    cp(7).wait()
"""


def test_gl08_factory_slot_violations_fire():
    findings = [f for f in lint(GL08_FACTORY_BAD) if f.rule == "GL08"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 3, msgs
    assert "SAME semaphore" in msgs
    assert "restarted" in msgs
    assert "all control paths" in msgs


def test_gl08_factory_overlap_idiom_quiet():
    assert not [f for f in lint(GL08_FACTORY_GOOD) if f.rule == "GL08"]


# ---------------------------------------------------------------------------
# GL09 — shard_map contract
# ---------------------------------------------------------------------------

GL09_BAD = """
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def local(a, b):
    return a + b


def run(x, y, mesh, axis="shard"):
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axis, None), P(axis), P()),
                   out_specs=P("replicas"))
    return fn(x, y)
"""


def test_gl09_fires_on_arity_and_axis_mismatch():
    findings = [f for f in lint(GL09_BAD) if f.rule == "GL09"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "3 entries" in msgs and "2 positional" in msgs
    assert "'replicas'" in msgs
    src_ok = GL09_BAD.replace(", P()),", "),") \
                     .replace('P("replicas")', "P(axis)")
    assert not [f for f in lint(src_ok) if f.rule == "GL09"]
    # a bare P(...) in_specs is a valid pytree PREFIX broadcast over
    # every argument — never an arity finding
    src_prefix = GL09_BAD.replace("(P(axis, None), P(axis), P()),",
                                  "P(axis, None),") \
                         .replace('P("replicas")', "P(axis)")
    assert not [f for f in lint(src_prefix) if f.rule == "GL09"]


GL09_TUPLE = """
from jax.sharding import PartitionSpec as P
from jax import shard_map
from raft_tpu.parallel.mesh import hier_mesh

HIER_AXIS_NAMES = ("dcn", "ici")
MESH = hier_mesh(4, 2, axis_names=HIER_AXIS_NAMES)


def local(v):
    return v


def run(x):
    fn = shard_map(local, mesh=MESH,
                   in_specs=(P(HIER_AXIS_NAMES, None),),
                   out_specs=P(("dcn", "ici"), None), check_vma=False)
    return fn(x)
"""


def test_gl09_resolves_tuple_axis_consts_via_mesh_binding():
    # mesh axes come from a module-level hier_mesh binding whose
    # axis_names is a tuple constant; P() joint-sharding over the tuple
    # (literal or via the same constant) resolves against them
    assert not [f for f in lint(GL09_TUPLE) if f.rule == "GL09"]
    typo = GL09_TUPLE.replace('out_specs=P(("dcn", "ici")',
                              'out_specs=P(("dcn", "icy")')
    findings = [f for f in lint(typo) if f.rule == "GL09"]
    assert len(findings) == 1
    assert "'icy'" in findings[0].message
    assert "'dcn'" in findings[0].message and "'ici'" in findings[0].message


# ---------------------------------------------------------------------------
# GL10 — facade bypass
# ---------------------------------------------------------------------------

GL10_BAD = """
from jax import lax


def merge(vals, axis_name):
    s = lax.psum(vals, axis_name)
    m = lax.pmax(vals, axis_name)
    return s + m
"""


def test_gl10_fires_outside_the_facade_module():
    findings = [f for f in lint(GL10_BAD, path="raft_tpu/parallel/fake.py")
                if f.rule == "GL10"]
    assert len(findings) == 2
    assert all("bypasses" in f.message for f in findings)
    # the facade itself and non-raft_tpu paths are exempt
    assert not [f for f in lint(GL10_BAD,
                                path="raft_tpu/parallel/comms.py")
                if f.rule == "GL10"]
    assert not [f for f in lint(GL10_BAD, path="tools/fake.py")
                if f.rule == "GL10"]
    # axis_index carries no payload: not a bypass
    src = GL10_BAD.replace("lax.psum", "lax.axis_index") \
                  .replace("lax.pmax", "lax.axis_index")
    assert not [f for f in lint(src, path="raft_tpu/parallel/fake.py")
                if f.rule == "GL10"]


# ---------------------------------------------------------------------------
# GL11 — int-overflow hazards in id arithmetic
# ---------------------------------------------------------------------------

GL11_BAD = """
import jax.numpy as jnp
import numpy as np


def remap(ids, rank, shard_rows):
    gids = ids.astype(jnp.int32) + rank.astype(jnp.int32) * shard_rows
    return gids


def iota(n):
    row_ids = jnp.arange(n)
    return row_ids


def host_math(shard, rows):
    offs = np.int32(shard * rows)
    return offs
"""

GL11_GOOD = """
import jax.numpy as jnp
from raft_tpu.core import ids as _ids


def remap(ids, rank, shard_rows, n_total):
    return _ids.global_ids(rank, shard_rows, ids, n_total=n_total)


def iota(n):
    row_ids = _ids.make_ids(n)
    return row_ids


def small_stuff(k, dim):
    mask = jnp.arange(k)          # not an id binding: no finding
    probes = jnp.arange(dim, dtype=jnp.int32) * 2
    return mask, probes
"""


def test_gl11_fires_on_id_overflow_hazards():
    findings = [f for f in lint(GL11_BAD) if f.rule == "GL11"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 3
    assert "global-id arithmetic" in msgs
    assert "default-dtype jnp.arange" in msgs
    assert "int32()" in msgs


def test_gl11_quiet_on_policy_helpers_and_small_iotas():
    assert not [f for f in lint(GL11_GOOD) if f.rule == "GL11"]
    # host np.arange building static tables is exempt by design
    src = """
import numpy as np

def sel(S):
    s_idx = np.arange(S)
    return s_idx
"""
    assert not [f for f in lint(src) if f.rule == "GL11"]


# ---------------------------------------------------------------------------
# GL12 — accumulator narrowing
# ---------------------------------------------------------------------------

GL12_BAD = """
import jax.numpy as jnp


def lut(q, cb):
    cbq = cb.astype(jnp.bfloat16)
    d1 = jnp.einsum("sp,skp->sk", q, cbq)
    d2 = jnp.dot(q, cb.astype(jnp.float8_e4m3fn))
    acc = jnp.sum(q.astype(jnp.bfloat16))
    return d1, d2, acc
"""

GL12_GOOD = """
import jax.numpy as jnp


def lut(q, cb):
    d = jnp.einsum("sp,skp->sk", q, cb.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    up = jnp.dot(q, cb.astype(jnp.bfloat16).astype(jnp.float32),
                 preferred_element_type=jnp.float32)
    s = jnp.sum(q.astype(jnp.bfloat16), dtype=jnp.float32)
    plain = jnp.dot(q, cb)       # f32 operands: no finding
    return d, up, s, plain
"""


def test_gl12_fires_on_narrowed_contractions():
    findings = [f for f in lint(GL12_BAD) if f.rule == "GL12"]
    assert len(findings) == 3
    assert all("preferred_element_type" in f.message for f in findings)


def test_gl12_quiet_on_pinned_accumulators():
    assert not [f for f in lint(GL12_GOOD) if f.rule == "GL12"]


# ---------------------------------------------------------------------------
# GL13 — sentinel safety
# ---------------------------------------------------------------------------

GL13_BAD = """
import jax.numpy as jnp


def bad_inf(mask, ids):
    return jnp.where(mask, jnp.inf, ids)


def bad_arith(mask, raw, base):
    ids = jnp.where(mask, raw, -1)
    offs = ids + base
    return offs
"""

GL13_GOOD = """
import jax.numpy as jnp


def guarded(mask, raw, base):
    ids = jnp.where(mask, raw, -1)
    return jnp.where(ids >= 0, ids + base, -1)


def float_sentinels(mask, dists):
    return jnp.where(mask, jnp.inf, dists)  # float array: fine
"""


def test_gl13_fires_on_sentinel_misuse():
    findings = [f for f in lint(GL13_BAD) if f.rule == "GL13"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "upcasts ids to float" in msgs
    assert "without a >= 0 guard" in msgs


def test_gl13_quiet_on_guarded_idioms():
    assert not [f for f in lint(GL13_GOOD) if f.rule == "GL13"]


# ---------------------------------------------------------------------------
# GL14 — Pallas per-grid-step resource budgets
# ---------------------------------------------------------------------------

GL14_BAD = """
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

_FAT = 4096


def kern(x_ref, o_ref):
    o_ref[:] = x_ref[:]


def fat_caller(x):
    return pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec((_FAT, 2048), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        scratch_shapes=[pltpu.SMEM((1024, 1024), jnp.int32)],
    )(x)
"""


def test_gl14_fires_on_budget_breaches():
    findings = [f for f in lint(GL14_BAD, path="raft_tpu/ops/fake.py")
                if f.rule == "GL14"]
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "VMEM footprint" in msgs and "16 MB" in msgs
    assert "SMEM-resident" in msgs
    # dynamic block shapes defer to the runtime budget: no finding
    src_ok = GL14_BAD.replace("(_FAT, 2048)", "(bq, 2048)") \
                     .replace("pltpu.SMEM((1024, 1024), jnp.int32)",
                              "pltpu.SMEM((8, 128), jnp.int32)")
    assert not [f for f in lint(src_ok, path="raft_tpu/ops/fake.py")
                if f.rule == "GL14"]
    # an over-budget SMEM-resident BLOCK fires even with no SMEM
    # scratch allocation at all (regression: the check must run after
    # the whole-function sweep, not only inside the scratch branch)
    src_blk = GL14_BAD.replace("(_FAT, 2048)", "(8, 128)") \
                      .replace(
        "in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],",
        "in_specs=[pl.BlockSpec((1024, 1024), lambda i: (i, 0),\n"
        "                       memory_space=pltpu.SMEM)],") \
                      .replace(
        "scratch_shapes=[pltpu.SMEM((1024, 1024), jnp.int32)],", "")
    blk = [f for f in lint(src_blk, path="raft_tpu/ops/fake.py")
           if f.rule == "GL14"]
    assert len(blk) == 1 and "SMEM-resident" in blk[0].message


def test_gl14_quiet_on_the_existing_kernels():
    """The three shipped streaming kernels' BlockSpecs stay under the
    static budget check (their block shapes are parameter-dynamic and
    measured VMEM-safe — the satellite acceptance case)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = graftlint.lint_paths(
        [os.path.join(root, "raft_tpu", "ops", "pallas_kernels.py")],
        select={"GL14"})
    assert not findings, "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# GL15 — streaming-tier dispatch without an admission guard
# ---------------------------------------------------------------------------

GL15_BAD = """
from raft_tpu.ops import pallas_kernels as _pk


def refine(ds, q, cand, k):
    return _pk.gather_refine_topk(ds, q, cand, k, "l2")
"""

GL15_GOOD = """
from raft_tpu.neighbors import ivf_common as ic
from raft_tpu.ops import pallas_kernels as _pk


def refine(ds, q, cand, k):
    if not ic.gather_refine_mem_ok(ds.shape[0], ds.shape[1]):
        return None
    return _pk.gather_refine_topk(ds, q, cand, k, "l2")
"""


def test_gl15_fires_on_unguarded_kernel_dispatch():
    findings = [f for f in lint(GL15_BAD) if f.rule == "GL15"]
    assert len(findings) == 1
    assert "admission guard" in findings[0].message
    # guarded module: quiet
    assert not [f for f in lint(GL15_GOOD) if f.rule == "GL15"]
    # outside raft_tpu/ (tools, tests): no contract
    assert not [f for f in lint(GL15_BAD, path="tools/fake.py")
                if f.rule == "GL15"]
    # the defining module itself is exempt
    assert not [f for f in lint(GL15_BAD,
                                path="raft_tpu/ops/pallas_kernels.py")
                if f.rule == "GL15"]


# ISSUE 12: a FILTERED fused dispatch (filter_bytes operand) is still a
# streaming-kernel dispatch — without any admission guard it fires; the
# new filtered_scan_mem_ok guard satisfies the contract (_mem_ok suffix
# registration, same convention as every tier).
GL15_FILTERED_BAD = """
from raft_tpu.ops import pallas_kernels as _pk


def filtered_scan(seg_list, qv, codes, ids, norms, ctr, cb, fbytes):
    return _pk.ivfpq_lut_scan_topk(
        seg_list, qv, codes, ids, norms, ctr, cb, "l2",
        pq_bits=8, pq_dim=16, L=1024, filter_bytes=fbytes)
"""

GL15_FILTERED_GOOD = """
from raft_tpu.neighbors import ivf_common as ic
from raft_tpu.ops import pallas_kernels as _pk


def filtered_scan(seg_list, qv, codes, ids, norms, ctr, cb, fbytes,
                  n_lists, L):
    if not ic.filtered_scan_mem_ok(n_lists, L):
        return None
    return _pk.ivfpq_lut_scan_topk(
        seg_list, qv, codes, ids, norms, ctr, cb, "l2",
        pq_bits=8, pq_dim=16, L=L, filter_bytes=fbytes)
"""


def test_gl15_filtered_dispatch_snippets():
    findings = [f for f in lint(GL15_FILTERED_BAD) if f.rule == "GL15"]
    assert len(findings) == 1, findings
    assert not [f for f in lint(GL15_FILTERED_GOOD) if f.rule == "GL15"]


# ISSUE 12: the masked-sentinel epilogue — the filter mask joins the
# validity mask BEFORE the where that pours the -1 sentinel, and any
# downstream id arithmetic keeps the >= 0 guard. Folding the filter by
# OFFSETTING sentinel-bearing ids is the bug GL13 exists for.
GL13_FILTER_EPILOGUE_BAD = """
import jax.numpy as jnp


def fold_filter_by_offset(keep, raw, base):
    ids = jnp.where(keep, raw, -1)
    gids = ids + base
    return gids
"""

GL13_FILTER_EPILOGUE_GOOD = """
import jax.numpy as jnp


def masked_sentinel_epilogue(keep, raw, key):
    valid = (raw >= 0) & keep
    ids = jnp.where(valid, raw, -1)
    key = jnp.where(valid, key, jnp.inf)
    return key, ids


def guarded_offset(keep, raw, base):
    ids = jnp.where(keep, raw, -1)
    return jnp.where(ids >= 0, ids + base, -1)
"""


def test_gl13_filter_epilogue_snippets():
    findings = [f for f in lint(GL13_FILTER_EPILOGUE_BAD)
                if f.rule == "GL13"]
    assert len(findings) == 1, findings
    assert "without a >= 0 guard" in findings[0].message
    assert not [f for f in lint(GL13_FILTER_EPILOGUE_GOOD)
                if f.rule == "GL13"]


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_line_suppression_is_per_rule():
    src = """
import os

def wanted():
    force = os.environ.get("X", "auto")  # graftlint: disable=GL02
    return force == "always"
"""
    assert not lint(src)
    # wrong rule id on the line does NOT silence GL02
    assert lint(src.replace("disable=GL02", "disable=GL01"))


def test_fn_scope_suppression():
    src = """
import jax
import numpy as np

@jax.jit
def f(x):  # graftlint: disable-fn=GL01
    return np.asarray(x), x.item()

@jax.jit
def g(x):
    return np.asarray(x)
"""
    findings = [f for f in lint(src) if f.rule == "GL01"]
    assert len(findings) == 1  # only g's — f is scope-suppressed


def test_fn_suppression_anchors_to_decorated_def():
    """Regression: disable-fn above a decorator stack (or trailing on a
    decorator line) applies to the function it documents, not to the
    decorator expression."""
    src = """
import functools
import jax
import numpy as np


# graftlint: disable-fn=GL01
@functools.partial(jax.jit, static_argnames=("k",))
def f(x, k):
    return np.asarray(x)


@jax.jit  # graftlint: disable-fn=GL01
def g(x):
    return np.asarray(x)


@jax.jit
def h(x):
    return np.asarray(x)
"""
    findings = [f for f in lint(src) if f.rule == "GL01"]
    assert len(findings) == 1
    assert "(h)" in findings[0].message
    # a TRAILING comment on the statement above the stack must NOT leak
    # into the next function
    src2 = src.replace(
        "# graftlint: disable-fn=GL01\n@functools",
        "y = 1  # graftlint: disable-fn=GL01\n@functools")
    assert len([f for f in lint(src2) if f.rule == "GL01"]) == 2


def test_disable_all():
    src = """
import os

def wanted():
    return os.environ.get("X") == "always"  # graftlint: disable=all
"""
    assert not lint(src)


def test_every_rule_has_a_suppressible_finding():
    """Meta-check: each rule id observed above responds to its own
    line suppression (guards the Finding.line anchoring)."""
    cases = {
        "GL01": (GL01_BAD, "    v = x.item()",
                 "    v = x.item()  # graftlint: disable=GL01"),
        "GL04": (GL04_BAD, "def build(dataset):",
                 "def build(dataset):  # graftlint: disable=GL04"),
        "GL07": (GL07_BAD,
                 '    return lax.ppermute(x, "shard", '
                 "perm=[(0, 1), (1, 1), (2, 0)])",
                 '    return lax.ppermute(x, "shard", '
                 "perm=[(0, 1), (1, 1), (2, 0)])"
                 "  # graftlint: disable=GL07"),
        "GL08": (GL08_BAD,
                 "    cp.start()\n    o_ref[:] = o_ref[:] * 2.0",
                 "    cp.start()  # graftlint: disable=GL08\n"
                 "    o_ref[:] = o_ref[:] * 2.0"),
        "GL10": (GL10_BAD, "    s = lax.psum(vals, axis_name)",
                 "    s = lax.psum(vals, axis_name)"
                 "  # graftlint: disable=GL10"),
        "GL11": (GL11_BAD, "    row_ids = jnp.arange(n)",
                 "    row_ids = jnp.arange(n)"
                 "  # graftlint: disable=GL11"),
        "GL13": (GL13_BAD, "    offs = ids + base",
                 "    offs = ids + base  # graftlint: disable=GL13"),
        "GL15": (GL15_BAD,
                 '    return _pk.gather_refine_topk(ds, q, cand, k, "l2")',
                 '    return _pk.gather_refine_topk(ds, q, cand, k, "l2")'
                 "  # graftlint: disable=GL15"),
        "GL16": (GL16_BAD, "        return self._total",
                 "        return self._total"
                 "  # graftlint: disable=GL16"),
        "GL17": (GL17_BAD, "    t = threading.Thread(target=fn)",
                 "    t = threading.Thread(target=fn)"
                 "  # graftlint: disable=GL17"),
        "GL18": (GL18_BAD, "    _tls.tenant = name",
                 "    _tls.tenant = name  # graftlint: disable=GL18"),
        "GL19": (GL19_BAD, '    logging.error("dumped")',
                 '    logging.error("dumped")'
                 "  # graftlint: disable=GL19"),
        "GL20": (GL20_BAD,
                 "def run_one(job):\n    fut = Future()",
                 "def run_one(job):\n"
                 "    fut = Future()  # graftlint: disable=GL20"),
    }
    for rule, (src, old, new) in cases.items():
        before = [f for f in lint(src) if f.rule == rule]
        after = [f for f in lint(src.replace(old, new)) if f.rule == rule]
        assert len(after) == len(before) - 1, rule



# ---------------------------------------------------------------------------
# GL16 — lock discipline
# ---------------------------------------------------------------------------

GL16_BAD = """
import threading

class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = {}
        self._total = 0

    def add(self, k, v):
        with self._lock:
            self._items[k] = v
            self._total += 1

    def size(self):
        return self._total

    def drop(self, k):
        self._items.pop(k, None)
"""

GL16_GOOD = """
import threading

class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = {}
        self.budget = 100
        self._name = "r"

    def add(self, k, v):
        with self._lock:
            self._items[k] = v
            self._grow_locked(k)

    def _grow_locked(self, k):
        self._items[k] = k

    def describe(self):
        return self._name, self.budget

    def busiest(self):
        with self._lock:
            return max(self._items, key=lambda k: self._items[k])
"""


def test_gl16_fires_on_unlocked_access_to_guarded_state():
    findings = [f for f in lint(GL16_BAD) if f.rule == "GL16"]
    assert len(findings) == 2
    assert any("_total" in f.message and "size" in f.message
               for f in findings)
    assert any("_items" in f.message and "drop" in f.message
               for f in findings)


def test_gl16_quiet_on_locked_helpers_constants_and_lambdas():
    """Public attrs, read-only-after-__init__ attrs, the locked-helper
    fixpoint, and inline lambdas inside a locked scope all stay quiet."""
    assert not [f for f in lint(GL16_GOOD) if f.rule == "GL16"]


# ---------------------------------------------------------------------------
# GL17 — thread lifecycle
# ---------------------------------------------------------------------------

GL17_BAD = """
import queue
import threading

def fire_and_forget(fn):
    t = threading.Thread(target=fn)
    t.start()
    return t

class Pump:
    def __init__(self, q):
        self._q = q
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            item = self._q.get()
            if item is None:
                break
"""

GL17_GOOD = """
import queue
import threading

class Prefetcher:
    def __init__(self):
        self._q = queue.Queue(4)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            try:
                item = self._q.get(timeout=0.05)
            except queue.Empty:
                continue

    def close(self):
        self._stop.set()
        self._thread.join(timeout=5.0)
"""


def test_gl17_fires_on_every_lifecycle_violation():
    findings = [f for f in lint(GL17_BAD) if f.rule == "GL17"]
    assert len(findings) == 3
    msgs = " | ".join(f.message for f in findings)
    assert "daemon=" in msgs
    assert "close()/stop()" in msgs
    assert "stop flag" in msgs


def test_gl17_quiet_on_the_prefetcher_idiom():
    """The shipped ChunkPrefetcher/RowPrefetcher shape: daemon reader,
    stop event checked per iteration, timeout on the blocking get, and
    an owner close() that sets + joins."""
    assert not [f for f in lint(GL17_GOOD) if f.rule == "GL17"]


# ---------------------------------------------------------------------------
# GL18 — thread-local context hygiene
# ---------------------------------------------------------------------------

GL18_BAD = """
import threading

_tls = threading.local()

def set_tenant(name):
    _tls.tenant = name
    do_work()
"""

GL18_GOOD = """
import threading

_tls = threading.local()

class tenant_scope:
    def __init__(self, name):
        self._name = name

    def __enter__(self):
        self._prev = getattr(_tls, "tenant", None)
        _tls.tenant = self._name
        return self

    def __exit__(self, *exc):
        _tls.tenant = self._prev

def install_tenant(name):
    prev = getattr(_tls, "tenant", None)
    _tls.tenant = name
    return prev

def bump():
    _tls.n = getattr(_tls, "n", 0) + 1

def scoped(name):
    prev = install_tenant(name)
    try:
        do_work()
    finally:
        _tls.tenant = prev
"""


def test_gl18_fires_on_unrestored_tls_write():
    findings = [f for f in lint(GL18_BAD) if f.rule == "GL18"]
    assert len(findings) == 1
    assert "restore" in findings[0].message


def test_gl18_quiet_on_the_bracket_idioms():
    """The four shipped shapes: a CM whose __exit__ restores, the
    save-and-return low-level setter (trace.set_request), a pure
    self-update counter, and install + try/finally restore in one
    function."""
    assert not [f for f in lint(GL18_GOOD) if f.rule == "GL18"]


# ---------------------------------------------------------------------------
# GL19 — signal-context safety
# ---------------------------------------------------------------------------

GL19_BAD = """
import logging
import signal
import threading

_lock = threading.Lock()

def _flush(path, payload):
    with _lock:
        with open(path, "w") as f:
            f.write(payload)
    logging.error("dumped")

def _handler(num, frame):
    _flush("/tmp/x", "payload")

signal.signal(signal.SIGTERM, _handler)
"""

GL19_GOOD = """
import logging
import os
import signal
import threading

_lock = threading.RLock()

def _flush(path, payload):
    with _lock:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, path)

def _handler(num, frame):
    _flush("/tmp/x", "payload")

signal.signal(signal.SIGTERM, _handler)

def not_on_the_signal_path():
    logging.error("fine here")
"""


def test_gl19_fires_on_non_reentrant_calls_on_signal_paths():
    findings = [f for f in lint(GL19_BAD) if f.rule == "GL19"]
    assert len(findings) == 3
    msgs = " | ".join(f.message for f in findings)
    assert "plain Lock" in msgs
    assert "logging" in msgs
    assert "torn file" in msgs


def test_gl19_quiet_on_rlock_tmp_rename_and_unreachable_code():
    assert not [f for f in lint(GL19_GOOD) if f.rule == "GL19"]


# ---------------------------------------------------------------------------
# GL20 — future resolution
# ---------------------------------------------------------------------------

GL20_BAD = """
from concurrent.futures import Future

def run_one(job):
    fut = Future()
    if job.ready:
        fut.set_result(job.run())
    fut.result()

def run_two(job):
    fut = Future()
    try:
        fut.set_result(job.run())
    except KeyError:
        pass
    fut.result()
"""

GL20_GOOD = """
from concurrent.futures import Future

def handoff(work, q):
    fut = Future()
    q.put((work, fut))
    return fut

def branches(job):
    fut = Future()
    if job.ready:
        fut.set_result(job.run())
    else:
        fut.set_exception(RuntimeError("not ready"))
    return fut

def guarded(job):
    fut = Future()
    try:
        fut.set_result(job.run())
    except Exception as exc:
        fut.set_exception(exc)
    return fut
"""


def test_gl20_fires_on_paths_that_never_resolve():
    findings = [f for f in lint(GL20_BAD) if f.rule == "GL20"]
    assert len(findings) == 2
    assert all("every path" in f.message for f in findings)


def test_gl20_quiet_on_handoff_and_all_path_resolution():
    """A future that ESCAPES (queued/returned for a consumer to
    resolve — the server submit() handoff) is the consumer's contract;
    if/else and try/except shapes that resolve every path are quiet."""
    assert not [f for f in lint(GL20_GOOD) if f.rule == "GL20"]


# ---------------------------------------------------------------------------
# --jobs parallel analysis
# ---------------------------------------------------------------------------

def test_jobs_parallel_matches_sequential():
    """--jobs fans per-file analysis over a process pool; the merged,
    sorted finding set must be byte-identical to the sequential run."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    target = os.path.join(root, "tools", "graftlint")
    seq = graftlint.lint_paths([target])
    par = graftlint.lint_paths([target], jobs=2)
    assert [f.render() for f in par] == [f.render() for f in seq]


def test_cli_jobs_flag(tmp_path):
    bad = tmp_path / "raft_tpu_mod.py"
    bad.write_text(GL16_BAD)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", str(bad), "--jobs", "2",
         "--format", "json"],
        capture_output=True, text=True, cwd=root)
    assert p.returncode == 1
    rows = json.loads(p.stdout)
    assert [r["rule"] for r in rows] == ["GL16", "GL16"]
    p = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", str(bad), "--jobs", "-2"],
        capture_output=True, text=True, cwd=root)
    assert p.returncode == 2

# ---------------------------------------------------------------------------
# engine / CLI
# ---------------------------------------------------------------------------

def test_select_filters_rules():
    findings = lint(GL01_BAD + GL04_BAD, select={"GL04"})
    assert rules_of(findings) == ["GL04"]


def test_repo_tree_is_clean():
    """The acceptance gate: zero unsuppressed findings on raft_tpu/."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = graftlint.lint_paths([os.path.join(root, "raft_tpu")])
    assert not findings, "\n".join(f.render() for f in findings)


def test_cli_json_and_exit_codes(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = tmp_path / "neighbors"
    bad.mkdir()
    (bad / "mod.py").write_text(GL04_BAD)
    env = dict(os.environ, PYTHONPATH=root)
    p = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", str(bad), "--format",
         "json"], capture_output=True, text=True, cwd=root, env=env)
    assert p.returncode == 1
    payload = json.loads(p.stdout)
    assert {f["rule"] for f in payload} == {"GL04"}
    (bad / "mod.py").write_text(GL04_GOOD)
    p = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", str(bad)],
        capture_output=True, text=True, cwd=root, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "clean" in p.stdout


def test_cli_report_artifact(tmp_path):
    """--report writes the CI JSON artifact beside the normal output."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = tmp_path / "neighbors"
    bad.mkdir()
    (bad / "mod.py").write_text(GL04_BAD)
    report = tmp_path / "report.json"
    env = dict(os.environ, PYTHONPATH=root)
    p = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", str(bad),
         "--report", str(report)],
        capture_output=True, text=True, cwd=root, env=env)
    assert p.returncode == 1
    doc = json.loads(report.read_text())
    assert doc["count"] == 2
    assert {f["rule"] for f in doc["findings"]} == {"GL04"}
    assert set(doc["rules"]) >= {"GL01", "GL06", "GL10"}


def _git(repo, *args):
    p = subprocess.run(
        ["git", "-c", "user.email=ci@test", "-c", "user.name=ci",
         *args], capture_output=True, text=True, cwd=repo)
    assert p.returncode == 0, (args, p.stdout, p.stderr)
    return p.stdout


def test_cli_changed_lints_only_modified_files(tmp_path):
    """--changed scopes the run to files modified vs merge-base(HEAD,
    main): pre-existing findings elsewhere in the tree don't block."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo = tmp_path / "repo"
    pkg = repo / "raft_tpu" / "neighbors"
    pkg.mkdir(parents=True)
    (pkg / "legacy.py").write_text(GL04_BAD)   # bad, but NOT changed
    (pkg / "mod.py").write_text("def helper(x):\n    return x\n")
    _git(repo, "init", "-q")
    _git(repo, "checkout", "-q", "-b", "main")
    _git(repo, "add", "-A")
    _git(repo, "commit", "-q", "-m", "seed")
    _git(repo, "checkout", "-q", "-b", "feature")

    env = dict(os.environ, PYTHONPATH=root)

    def run_changed(*extra):
        return subprocess.run(
            [sys.executable, "-m", "tools.graftlint", "--changed",
             *extra], capture_output=True, text=True, cwd=repo, env=env)

    # clean edit on the feature branch → exit 0 despite legacy.py
    (pkg / "mod.py").write_text("def helper(x):\n    return x + 1\n")
    _git(repo, "commit", "-aqm", "clean edit")
    p = run_changed()
    assert p.returncode == 0, p.stdout + p.stderr
    assert "1 file(s) in scope" in p.stdout

    # bad UNCOMMITTED edit → exit 1, findings only in mod.py
    (pkg / "mod.py").write_text(GL04_BAD)
    p = run_changed("--format", "json")
    assert p.returncode == 1
    payload = json.loads(p.stdout)
    assert payload and all("mod.py" in f["path"] for f in payload)

    # a brand-new UNTRACKED bad file is in scope even when the CLI runs
    # from a subdirectory (git ls-files --others is cwd-relative)
    (pkg / "mod.py").write_text("def helper(x):\n    return x + 1\n")
    (pkg / "fresh.py").write_text(GL04_BAD)
    p = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--changed",
         os.path.join(str(repo), "raft_tpu"), "--format", "json"],
        capture_output=True, text=True, cwd=str(pkg), env=env)
    assert p.returncode == 1, p.stdout + p.stderr
    assert all("fresh.py" in f["path"] for f in json.loads(p.stdout))

    # full run (no --changed) still sees legacy.py
    p = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "raft_tpu", "--format",
         "json"], capture_output=True, text=True, cwd=repo, env=env)
    assert p.returncode == 1
    assert any("legacy.py" in f["path"] for f in json.loads(p.stdout))


def test_cli_baseline_gates_only_new_findings(tmp_path):
    """--baseline records current findings and gates only NEW ones —
    the mechanism that lets a future rule land blocking without blanket
    suppressions (same reporter/exit codes as the plain run)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg = tmp_path / "repo" / "raft_tpu" / "neighbors"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(GL04_BAD)   # two legacy GL04 findings
    bl = tmp_path / "baseline.json"
    env = dict(os.environ, PYTHONPATH=root)
    repo = str(tmp_path / "repo")

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "tools.graftlint", "raft_tpu",
             "--baseline", str(bl), *extra],
            capture_output=True, text=True, cwd=repo, env=env)

    # a missing baseline file is an empty baseline: everything gates
    p = run()
    assert p.returncode == 1 and "NEW finding" in p.stdout

    # record, then the gated run is clean despite the legacy findings
    p = run("--update-baseline")
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.loads(bl.read_text())
    assert doc["count"] == 2
    p = run()
    assert p.returncode == 0, p.stdout + p.stderr
    assert "2 baseline finding(s) suppressed" in p.stdout

    # line drift above a legacy finding does NOT un-baseline it...
    (pkg / "mod.py").write_text("import os\n\n\n" + GL04_BAD)
    p = run()
    assert p.returncode == 0, p.stdout + p.stderr

    # ...but a brand-new finding still gates, and only IT is reported
    (pkg / "mod.py").write_text(GL04_BAD + "\n\ndef fit(x):\n    return x\n")
    report = tmp_path / "report.json"
    p = run("--format", "json", "--report", str(report))
    assert p.returncode == 1
    payload = json.loads(p.stdout)
    assert len(payload) == 1 and "fit" in payload[0]["message"]
    rep = json.loads(report.read_text())
    assert rep["count"] == 1 and rep["baseline_suppressed"] == 2

    # --update-baseline without --baseline is a usage error
    p = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "raft_tpu",
         "--update-baseline"],
        capture_output=True, text=True, cwd=repo, env=env)
    assert p.returncode == 2

    # --update-baseline refuses the --changed scope (recording only the
    # changed files would ERASE unchanged files' baseline entries)
    p = run("--update-baseline", "--changed")
    assert p.returncode == 2
    assert "--changed" in p.stderr

    # an update run still writes the --report artifact (full finding set)
    rep2 = tmp_path / "update_report.json"
    p = run("--update-baseline", "--report", str(rep2))
    assert p.returncode == 0
    doc2 = json.loads(rep2.read_text())
    assert doc2["count"] == 3 and doc2["baseline_suppressed"] == 0
