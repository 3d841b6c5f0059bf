#!/bin/bash
# CI test runner (reference: ci/test_python.sh — pytest for pylibraft :43
# and raft-dask :55). Runs the whole suite on a virtual 8-device CPU mesh
# so every sharded/shard_map code path executes for real without TPU
# hardware (tests/conftest.py pins the platform; these env vars make the
# intent explicit and cover non-pytest entry points).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

echo "== graftlint static analysis (blocking; CPU-only, no device) =="
# cache-bust-proof by construction: a pure-stdlib AST pass over the
# tree — no XLA compile cache, no pytest cache, no device backend, so
# it cannot go stale or flake with the environment. Zero unsuppressed
# findings is the gate (tools/graftlint, docs/developer_guide.md);
# covers GL01–GL05, the SPMD/DMA pass GL06–GL10, the capacity/
# numeric-safety pass GL11–GL15, and the concurrency pass GL16–GL20
# (lock discipline, thread lifecycle, TLS hygiene, signal-context
# safety, future resolution). The JSON report is the CI artifact
# (per-finding rule/path/line); --jobs fans the per-file analysis over
# the runner's cores with a single shared AST walk per file.
python -m tools.graftlint raft_tpu --jobs 0 \
    --report /tmp/graftlint_report.json
echo "graftlint report artifact: /tmp/graftlint_report.json"

echo "== capacity prover (device-free eval_shape proofs, n = 2.2e9) =="
# the runtime half of the capacity pass: every public search entry,
# the sharded merge tier, and build_chunked's assignment/encode pass
# traced at billion-scale synthetic shapes (ShapeDtypeStruct — zero
# bytes allocated) and walked for int32-indexes-≥2³¹-axis eqns
# (obs.sanitize.assert_billion_safe; tools/capacity_prove.py)
JAX_PLATFORMS=cpu python -m tools.capacity_prove \
    --report /tmp/capacity_prove_report.json
echo "capacity report artifact: /tmp/capacity_prove_report.json"

echo "== raft_tpu unit+integration tests (8-device CPU mesh) =="
python -m pytest tests/ -q "$@"

echo "== sanitizer-mode subset (RAFT_TPU_SANITIZE=1: rank-promotion raise"
echo "   + debug_nans + transfer guards + recompile budgets + the"
echo "   collective-schedule checker over the parallel/distributed suites,"
echo "   + the lock-order tracker over the threaded serving plane) =="
# test_concurrency.py is deliberately LAST: its closing test asserts
# the process-wide lock-acquisition graph the preceding serve/quality/
# tiered modules recorded is cycle-free and blocking-free, and its
# seeded AB/BA negative control proves the detector actually fires
RAFT_TPU_SANITIZE=1 python -m pytest \
    tests/test_sanitize.py tests/test_graftlint.py tests/test_core.py \
    tests/test_capacity.py \
    tests/test_parallel.py tests/test_parallel_ivf.py \
    tests/test_ring_topk.py tests/test_build_distributed.py \
    tests/test_serve.py tests/test_quality.py tests/test_tiered.py \
    tests/test_concurrency.py \
    -q -p no:cacheprovider

echo "== driver contract: entry() compiles, dryrun_multichip(8) executes =="
python - <<'EOF'
import jax
import __graft_entry__ as g

fn, args = g.entry()
jax.jit(fn).lower(*args)  # compile-check single chip
print("entry() lowers OK")
comms = g.dryrun_multichip(8)
# ISSUE 5: the dryrun must hand back nonzero comm counters for the
# sharded-kNN (allgather) and distributed-kmeans (allreduce) legs,
# with per-axis attribution on the 2-axis DCN×ICI mesh
assert comms, "dryrun returned no comms snapshot"
assert comms.get("comms.ops{axis=shard,op=allgather}", 0) > 0, comms
assert comms.get("comms.ops{axis=shard,op=allreduce}", 0) > 0, comms
assert comms.get("comms.bytes{axis=shard,op=allreduce}", 0) > 0, comms
assert comms.get("comms.ops{axis=ici,op=allreduce}", 0) > 0, comms
assert comms.get("comms.ops{axis=dcn,op=allreduce}", 0) > 0, comms
# ISSUE 8: the ring merge tier must run (7 counted hops per merge on
# the 8-device mesh) and its merge-phase bytes must beat the allgather
# tier's by >= 2x at n_dev=8 on the scaling legs (rows self-stamped)
assert comms.get("comms.ops{axis=shard,op=ring_topk}", 0) > 0, comms
rows = comms.get("scaling")
assert rows, "dryrun returned no MULTICHIP_SCALING rows"
assert {r["n_dev"] for r in rows} == {2, 4, 8}, rows
assert all(r["measured_at"] and r["git_commit"] for r in rows), rows
for leg in ("strong", "weak"):
    by = {r["merge"]: r["merge_bytes"] for r in rows
          if r.get("leg") == leg and r["n_dev"] == 8}
    assert 2 * by["ring"] <= by["allgather"], (leg, by)
# ISSUE 19: the hierarchical ICI→DCN merge rows — per-axis attribution
# nonzero on BOTH axes, DCN traffic exactly the k-survivor all-to-all
# model and strictly below the flat single-ring's cross-pod bytes, on
# both 2-D carvings (2x4 and 4x2) of the 8-device mesh
hrows = [r for r in rows if r.get("kind") == "hier"]
assert {r["mesh"] for r in hrows} == {"2x4", "4x2"}, rows
for r in hrows:
    assert r["dcn_bytes"] == r["survivor_model_bytes"] > 0, r
    assert r["ici_bytes"] > 0, r
    assert r["dcn_bytes"] < r["flat_ring_bytes"], r
# ISSUE 13: the distributed-build legs — weak+strong build-throughput
# rows at n_dev ∈ {2,4,8}, every build's comms ALLGATHERV-ONLY (codes/
# ids never cross shards), overlapped encode wall < serialized
# copy+encode on every leg (the dryrun itself also asserts this plus
# distributed == build_chunked sha-identity — a regression fails the
# run, not just this re-check)
brows = comms.get("build")
assert brows, "dryrun returned no MULTICHIP_BUILD rows"
assert {r["n_dev"] for r in brows} == {2, 4, 8}, brows
assert all(r["allgatherv_only"] for r in brows), brows
assert all(r["measured_at"] and r["git_commit"] for r in brows), brows
assert all(r["vectors_per_s_per_chip"] > 0 for r in brows), brows
for leg in ("strong", "weak"):
    for nd in (2, 4, 8):
        by = {r["impl"]: r["wall_s"] for r in brows
              if r["leg"] == leg and r["n_dev"] == nd}
        assert by["prefetch"] < by["serial"], (leg, nd, by)
# ISSUE 15: the fleet-aggregation leg — per-host flight dumps merged
# into ONE clock-aligned view (shared run_id) whose per-collective
# straggler table names the injected straggler rank (the dryrun itself
# also asserts alignment ordering + skew; this re-checks the record)
fleet = comms.get("fleet")
assert fleet, "dryrun returned no MULTICHIP_FLEET view"
assert fleet["aligned_ok"] and fleet["merged_events"] > 0, fleet
assert len(fleet["hosts"]) == fleet["n_hosts"] == 4, fleet["hosts"]
ag = [s for s in fleet["stragglers"]
      if s["collective"] == "comms.allgatherv"]
assert ag, fleet["stragglers"]
assert ag[0]["slowest"] == f"rank{fleet['straggler_rank']}", ag[0]
assert ag[0]["skew_frac"] > 0.10, ag[0]
print("dryrun_multichip(8) OK; comms section:", len(comms) - 3,
      "series;", len(rows), "scaling rows;", len(brows), "build rows;",
      "fleet:", len(fleet["hosts"]), "hosts,",
      f"straggler {ag[0]['slowest']} at {ag[0]['skew_frac']:+.0%} skew")
EOF

echo "== ring top-k exchange kernel smoke (interpret mode, 8-dev mesh) =="
python - <<'EOF'
# the ACTUAL Pallas ring kernel (remote DMAs interpreted) vs the
# ppermute fallback the CPU dryrun uses: identical results by schedule
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from raft_tpu.ops import pallas_kernels as pk
from raft_tpu.parallel import make_mesh, merge_topk

mesh = make_mesh(axis_names=("shard",))
m, k, n_dev = 40, 8, 8
rng = np.random.default_rng(0)
vals = np.sort(rng.random((n_dev, m, k)).astype(np.float32), axis=-1)
ids = rng.integers(0, 10_000, (n_dev, m, k)).astype(np.int32)

def kernel_body(v, i):
    return pk.ring_topk_merge(v[0], i[0], k, "shard", n_dev,
                              select_min=True, interpret=True)

def fallback_body(v, i):
    return merge_topk(v[0], i[0], "shard", m, k, n_dev, True,
                      tier="ring", impl="ring_ppermute")

outs = {}
for name, body in (("kernel", kernel_body), ("fallback", fallback_body)):
    f = shard_map(body, mesh=mesh,
                  in_specs=(P("shard", None, None), P("shard", None, None)),
                  out_specs=(P("shard", None), P("shard", None)),
                  check_vma=False)
    gv, gi = f(jnp.asarray(vals), jnp.asarray(ids))
    outs[name] = (np.asarray(gv)[:m], np.asarray(gi)[:m])
np.testing.assert_array_equal(outs["kernel"][1], outs["fallback"][1])
np.testing.assert_allclose(outs["kernel"][0], outs["fallback"][0])
print("ring kernel smoke OK: interpret-mode remote-DMA ring == ppermute "
      "fallback on the 8-device mesh")
EOF

echo "== chaos lane (fault-injected OOM / SIGTERM;"
echo "   docs/developer_guide.md 'Robustness') =="
python - <<'EOF'
# 1. injected RESOURCE_EXHAUSTED during an oversampled search: the
#    degradation ladder must complete the request, record its path in
#    degrade.steps, and return results identical to the undegraded
#    search (batch splitting is exact per query).
import numpy as np
import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.robust import faults
from raft_tpu.neighbors import ivf_pq

rng = np.random.default_rng(0)
x = jnp.asarray(rng.random((3000, 32), dtype=np.float32))
idx = ivf_pq.build(x, ivf_pq.IndexParams(
    n_lists=16, pq_dim=16, seed=0, cache_reconstruction="never"))
sp = ivf_pq.SearchParams(n_probes=8, scan_mode="per_query")
d_ref, i_ref = ivf_pq.search(idx, x[:64], 40, sp)
reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False)
faults.install_plan({"faults": [
    {"site": "ivf_pq.search", "kind": "oom", "times": 1}]})
try:
    d_dg, i_dg = ivf_pq.search_resilient(idx, x[:64], 40, sp)
finally:
    faults.clear_plan()
    obs.disable()
np.testing.assert_array_equal(np.asarray(i_dg), np.asarray(i_ref))
snap = reg.snapshot()
step = snap["counters"].get(
    "degrade.steps{from=native,reason=resource_exhausted,"
    "site=ivf_pq.search,to=halve_batch}", 0)
assert step >= 1, snap["counters"]
assert snap["counters"].get("faults.fired{kind=oom,site=ivf_pq.search}",
                            0) >= 1, snap["counters"]
print("chaos OOM OK: ladder completed via halve_batch, results match, "
      "degrade.steps + faults.fired recorded")

# 1b. three injected OOMs walk halve_batch → bf16_lut → fp8_lut
#     (ISSUE 11's new rung): the request completes, the walk is
#     counted, and results equal the fp8-configuration run without
#     faults (the rung is the documented precision trade; batch
#     splitting stays exact).
import dataclasses

sp8 = dataclasses.replace(sp, lut_dtype="float8_e4m3")
d8a, i8a = ivf_pq.search(idx, x[:32], 40, sp8)
d8b, i8b = ivf_pq.search(idx, x[32:64], 40, sp8)
reg2 = MetricsRegistry()
obs.enable(registry=reg2, hbm=False)
faults.install_plan({"faults": [
    {"site": "ivf_pq.search", "kind": "oom", "times": 3}]})
try:
    d_f8, i_f8 = ivf_pq.search_resilient(idx, x[:64], 40, sp)
finally:
    faults.clear_plan()
    obs.disable()
np.testing.assert_array_equal(
    np.asarray(i_f8), np.concatenate([np.asarray(i8a), np.asarray(i8b)]))
c2 = reg2.snapshot()["counters"]
assert c2.get("degrade.steps{from=bf16_lut,reason=resource_exhausted,"
              "site=ivf_pq.search,to=fp8_lut}", 0) == 1, c2
print("chaos OOM OK (fp8 rung): 3 OOMs walked halve_batch -> bf16_lut "
      "-> fp8_lut; results equal the fault-free fp8 configuration")
EOF
python - <<'EOF'
# 2. injected SIGTERM mid-build_chunked, then resume=True: the resumed
#    index must be sha-identical to an uninterrupted build and the
#    resume.* counters must record the replay.
import hashlib, json, os, shutil, subprocess, sys, tempfile
import numpy as np

work = tempfile.mkdtemp(prefix="raft_chaos_")
data = os.path.join(work, "data.npy")
np.save(data, np.random.default_rng(7).random((4000, 32),
                                              dtype=np.float32))
ck = os.path.join(work, "ckpt")
child = """
import os, numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"
from raft_tpu.robust import faults
from raft_tpu.neighbors import ivf_pq
faults.install_plan({"faults": [{"site": "build.chunk_encode",
                                 "kind": "sigterm", "after": 3}]})
x = np.load(%r, mmap_mode="r")
ivf_pq.build_chunked(x, ivf_pq.IndexParams(n_lists=16, pq_dim=16, seed=0,
                                           cache_reconstruction="never"),
                     chunk_rows=500, checkpoint_dir=%r)
raise SystemExit("UNREACHABLE: the injected SIGTERM did not fire")
""" % (data, ck)
p = subprocess.run([sys.executable, "-c", child], capture_output=True,
                   text=True)
assert p.returncode != 0, "child survived the injected SIGTERM"
man = json.load(open(os.path.join(ck, "manifest.json")))
assert man["phase"] == "encode" and 0 < man["chunks_done"] < man["n_chunks"], man

from raft_tpu import obs
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.neighbors import ivf_pq

x = np.load(data, mmap_mode="r")
params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, seed=0,
                            cache_reconstruction="never")
reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False)
try:
    resumed = ivf_pq.build_chunked(x, params, chunk_rows=500,
                                   checkpoint_dir=ck, resume=True)
finally:
    obs.disable()
clean = ivf_pq.build_chunked(x, params, chunk_rows=500)

def sha(idx):
    h = hashlib.sha256()
    for name in ("centers", "centers_rot", "rotation", "codebooks",
                 "packed_codes", "packed_ids", "packed_norms",
                 "list_sizes"):
        h.update(np.ascontiguousarray(
            np.asarray(getattr(idx, name))).tobytes())
    return h.hexdigest()
assert sha(resumed) == sha(clean), \
    "resumed index differs from an uninterrupted build"
c = reg.snapshot()["counters"]
assert c.get("resume.attempts{site=ivf_pq.build_chunked}", 0) >= 1, c
assert c.get("resume.chunks_replayed{site=ivf_pq.build_chunked}",
             0) == man["chunks_done"], c
shutil.rmtree(work)
print(f"chaos SIGTERM OK: died at chunk {man['chunks_done']}, resumed "
      "sha-identical, resume.* counters recorded")
EOF
python - <<'EOF'
# 2b (ISSUE 13). injected SIGTERM mid-DISTRIBUTED-build, then per-shard
#    resume=True: the resumed sharded index must be sha-identical to an
#    uninterrupted distributed build, resume.* counters must record the
#    per-shard replay — and an IO error injected on a chunk read during
#    the resumed build must be retried under IO_POLICY
#    (retry.recovered{site=build.chunk_read} counted).
import json, os, shutil, subprocess, sys, tempfile
import numpy as np

work = tempfile.mkdtemp(prefix="raft_chaos_dbuild_")
data = os.path.join(work, "data.npy")
np.save(data, np.random.default_rng(17).random((2400, 24),
                                               dtype=np.float32))
ck = os.path.join(work, "ckpt")
child = """
import os, numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"
from raft_tpu.robust import faults
from raft_tpu.neighbors import ivf_pq
from raft_tpu.parallel import make_mesh
faults.install_plan({"faults": [{"site": "build.chunk_encode",
                                 "kind": "sigterm", "after": 5}]})
x = np.load(%r, mmap_mode="r")
ivf_pq.build_distributed(
    x, ivf_pq.IndexParams(n_lists=16, pq_dim=16, seed=0,
                          cache_reconstruction="never"),
    mesh=make_mesh(), chunk_rows=200, checkpoint_dir=%r)
raise SystemExit("UNREACHABLE: the injected SIGTERM did not fire")
""" % (data, ck)
p = subprocess.run([sys.executable, "-c", child], capture_output=True,
                   text=True)
assert p.returncode != 0, "child survived the injected SIGTERM"
man = json.load(open(os.path.join(ck, "manifest.json")))
assert man["phase"] == "encode" and man["n_shards"] == 8, man
done = man["shard_chunks_done"]
assert 0 < sum(done) < man["n_shards"] * 2, man
assert man.get("fingerprint_s") is not None, man

from raft_tpu import obs
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.neighbors import ivf_pq
from raft_tpu.parallel import index_sha16, make_mesh
from raft_tpu.robust import faults

x = np.load(data, mmap_mode="r")
params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, seed=0,
                            cache_reconstruction="never")
mesh = make_mesh()
reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False)
faults.install_plan({"faults": [{"site": "build.chunk_read",
                                 "kind": "error", "times": 1}]})
try:
    resumed = ivf_pq.build_distributed(x, params, mesh=mesh,
                                       chunk_rows=200,
                                       checkpoint_dir=ck, resume=True)
finally:
    faults.clear_plan()
    obs.disable()
clean = ivf_pq.build_distributed(x, params, mesh=mesh, chunk_rows=200)
assert index_sha16(resumed) == index_sha16(clean), \
    "resumed distributed build differs from an uninterrupted one"
c = reg.snapshot()["counters"]
site = "{site=ivf_pq.build_distributed}"
assert c.get(f"resume.attempts{site}", 0) >= 1, c
assert c.get(f"resume.chunks_replayed{site}", 0) == sum(done), c
assert c.get("retry.recovered{site=build.chunk_read}", 0) >= 1, c
shutil.rmtree(work)
print(f"chaos distributed-build OK: died with shard chunks {done}, "
      "per-shard resume sha-identical, injected chunk-read IO error "
      "retried and recovered")
EOF

echo "== observability smoke (RAFT_TPU_BENCH_OBS=1, instrumented ivf_pq) =="
# bench.py measures a TPU only, so the CPU smoke drives its hard-leg
# config through the runner directly and folds the rows with bench._row
rm -f /tmp/raft_tpu_obs_smoke.jsonl
RAFT_TPU_BENCH_OBS=1 \
RAFT_TPU_BENCH_OBS_JSONL=/tmp/raft_tpu_obs_smoke.jsonl python - <<'EOF'
import json

import bench
from raft_tpu.bench import runner

runner.run_config(
    bench.hard_config(20_000, 500, ["ivf_pq"]), verbose=True,
    on_row=lambda r: bench.STATE["detail"].append(
        bench._row("sift-1m-hard-synth", r)))
with open("/tmp/raft_tpu_obs_bench.out", "w") as f:
    f.write(json.dumps(bench._payload()) + "\n")
EOF
python - <<'EOF'
import json

from raft_tpu.obs import load_jsonl

rows = load_jsonl("/tmp/raft_tpu_obs_smoke.jsonl")
names = {r["name"] for r in rows}
# the capture times whole-API calls; a span.ivf_pq.search.scan child
# appears only where the fused Pallas LUT tier runs (a TPU)
need = {"span.ivf_pq.search", "span.refine"}
missing = need - names
assert not missing, f"missing expected spans: {sorted(missing)}"
assert all(r["sum"] > 0 for r in rows
           if r["kind"] == "histogram" and r["name"] in need)
# the scan-dispatch counter must record which engine search() picked
disp = [r for r in rows if r["name"] == "ivf_pq.scan.dispatch"]
assert disp and all(r["value"] > 0 for r in disp), \
    f"ivf_pq.scan.dispatch counter missing: {sorted(names)}"
# ISSUE 12: the hard leg now carries filtered rows (the selectivity
# sweep) — the RETIRED filter_bitset fallback reason must stay at ZERO
# across every filtered leg for eligible shapes (a regression that
# re-disqualifies filtered searches from the fused tiers trips here),
# and filtered dispatch decisions carry the filtered=1 label
fb_rows = [r for r in rows if r["name"] == "ivf_pq.scan.fallback"
           and r["labels"].get("reason") == "filter_bitset"]
assert not fb_rows, \
    f"retired filter_bitset fallback reason resurfaced: {fb_rows}"
filt = [r for r in disp if r["labels"].get("filtered") == "1"]
assert filt and all(r["value"] > 0 for r in filt), \
    f"no filtered=1 scan dispatches recorded: {disp}"
# the prof.* roofline gauges must have landed in the captured series
prof = [r for r in rows if r["name"].startswith("prof.")]
assert {"prof.flops", "prof.bytes", "prof.bound"} <= \
    {r["name"] for r in prof}, sorted(names)
# ISSUE 9 acceptance: the smoke record's rows carry non-null cost
# columns + environment provenance (saved as the gate's record)
recs = [json.loads(ln) for ln in open("/tmp/raft_tpu_obs_bench.out")
        if ln.startswith("{")]
record = recs[-1]
assert record["detail"], "obs smoke produced no rows"
for r in record["detail"]:
    assert r.get("flops") and r.get("bytes_accessed"), r
    assert r.get("bound") in ("memory", "compute"), r
    assert r.get("env", {}).get("jax"), r
with open("/tmp/raft_tpu_obs_bench.json", "w") as f:
    json.dump(record, f, indent=1)
print(f"observability smoke OK: {len(rows)} series, spans "
      f"{sorted(n for n in names if n.startswith('span.'))}, dispatch "
      f"impls {sorted(r['labels'].get('impl') for r in disp)}; "
      f"{len(record['detail'])} rows with cost columns "
      f"(bound={sorted({r['bound'] for r in record['detail']})})")
EOF

echo "== benchdiff regression gate (ISSUE 9: unchanged record passes,"
echo "   faults-sleep-injected slowdown trips the gate) =="
# gate 1: the smoke record vs itself — an unchanged record must pass
python -m tools.benchdiff /tmp/raft_tpu_obs_bench.json \
    /tmp/raft_tpu_obs_bench.json \
    --md /tmp/raft_tpu_benchdiff_scoreboard.md \
    --json /tmp/raft_tpu_benchdiff_verdict.json
# gate 2 (self-test): re-measure one CPU-shaped leg clean and with a
# PR-7 fault-plan "sleep" injected at ivf_flat.search — the injected
# ≥20% qps regression must exit non-zero through the CLI gate
python - <<'EOF'
import json
import subprocess
import sys

from raft_tpu.bench import runner
from raft_tpu.robust import faults

cfg = {
    "dataset": {"name": "gate-smoke", "n": 20_000, "dim": 32,
                "n_queries": 500, "metric": "sqeuclidean"},
    "k": 10, "batch_size": 10_000,
    "index": [{"name": "ivf_flat.n64", "algo": "ivf_flat",
               "build_param": {"n_lists": 64},
               "search_params": [{"n_probes": 8}]}],
}

def measure():
    rows = runner.run_config(json.loads(json.dumps(cfg)), verbose=False)
    return {"detail": [
        {"dataset": r.dataset, "algo": r.algo, "index": r.index_name,
         "qps": r.qps, "recall": r.recall, "batch_size": r.batch_size,
         "search_param": r.search_param, "env": r.env} for r in rows]}

base = measure()
plan = faults.install_plan({"faults": [{"site": "ivf_flat.search",
                                        "kind": "sleep", "sleep_s": 0.3,
                                        "times": 0}]})
try:
    slow = measure()
finally:
    faults.clear_plan()
assert plan.fires().get("ivf_flat.search", 0) > 0, \
    "sleep fault never fired — the self-test measured nothing"
b, s = base["detail"][0]["qps"], slow["detail"][0]["qps"]
assert s < 0.8 * b, f"injected sleep only moved qps {b:.0f}->{s:.0f}"
json.dump(base, open("/tmp/raft_tpu_gate_base.json", "w"))
json.dump(slow, open("/tmp/raft_tpu_gate_slow.json", "w"))
for args, want in ((["/tmp/raft_tpu_gate_base.json"] * 2, 0),
                   (["/tmp/raft_tpu_gate_base.json",
                     "/tmp/raft_tpu_gate_slow.json"], 1)):
    p = subprocess.run([sys.executable, "-m", "tools.benchdiff"] + args,
                       capture_output=True, text=True)
    assert p.returncode == want, (args, want, p.returncode, p.stdout)
print(f"benchdiff gate OK: unchanged record passed; injected sleep "
      f"({b:,.0f} -> {s:,.0f} qps) tripped exit 1")
EOF
# informational: drift vs the committed baseline (never gates — CPU
# qps is machine-load-dependent across hosts; the env-stamp refusal
# and join are what this exercises)
python -m tools.benchdiff cpu_smoke /tmp/raft_tpu_obs_bench.json \
    --report-only --allow-env-mismatch | tail -5
python -m tools.obsdump /tmp/raft_tpu_benchdiff_verdict.json \
  | grep -q "Verdict" || { echo "obsdump failed on the verdict"; exit 1; }
echo "benchdiff scoreboard artifact: /tmp/raft_tpu_benchdiff_scoreboard.md"

echo "== distributed-build throughput baseline (ISSUE 13): committed"
echo "   vectors/s/chip rows pass a benchdiff self-compare =="
# the committed build_cpu_smoke record (tools/record_build_baseline.py:
# the MULTICHIP_BUILD legs as a bench-shaped record with environment
# provenance) against itself — proves the record joins, carries the
# env stamp, and an unchanged record passes the gate (exit 0 blocks)
python -m tools.benchdiff build_cpu_smoke build_cpu_smoke \
    --md /tmp/raft_tpu_build_baseline_scoreboard.md | tail -3

echo "== serving smoke (ISSUE 14/15: micro-batch server on the CPU backend,"
echo "   loadgen burst under recompile_budget(0) with request tracing AND"
echo "   the exposition endpoint live, tracing-overhead gate, mid-load"
echo "   /metrics scrape, exemplar -> obsdump --slowest drill-down, typed"
echo "   shedding, ladder OOM walk; docs/developer_guide.md 'Serving') =="
python - <<'EOF'
# start the server (buckets AOT-warmed, /metrics endpoint live), drive
# an open-loop burst tracing-OFF then the same burst tracing-ON (events
# + request contexts + exemplars) — BOTH under the PR-3 zero-recompile
# budget, with the ON step's p50 within the documented overhead bar —
# then scrape the endpoint mid-load, resolve the p99's exemplar trace
# ids through obsdump --slowest, overload behind a fault-injected stall
# (typed queue_full shedding) and OOM a batch (degrade-ladder walk)
import json, os, shutil, subprocess, sys, threading, urllib.request
import numpy as np
import jax.numpy as jnp

from raft_tpu import obs, serve
from raft_tpu.obs import flight, sanitize
from raft_tpu.obs.expo import parse_prometheus
from raft_tpu.obs.metrics import MetricsRegistry, exemplars_for_quantile
from raft_tpu.neighbors import ivf_pq
from raft_tpu.robust import faults
from raft_tpu.serve import loadgen

rng = np.random.default_rng(0)
x = rng.random((20_000, 32), dtype=np.float32)
idx = ivf_pq.build(jnp.asarray(x), ivf_pq.IndexParams(
    n_lists=64, pq_dim=16, seed=0, cache_reconstruction="never"))
reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False)
registry = serve.IndexRegistry(budget_bytes=4 << 30)
registry.admit("smoke", idx, params=ivf_pq.SearchParams(
    n_probes=8, scan_mode="per_query"), default_k=10)
server = serve.MicroBatchServer(registry, serve.ServerConfig(
    max_batch=16, queue_depth=64, linger_s=0.002, default_slo_s=1.0,
    expo_port=0))
with server:
    for j in range(5):  # settle anything warmup's zero-queries missed
        server.search("smoke", x[j], 10)
    # steady state, tracing OFF: the overhead baseline; a 300 qps
    # open-loop burst across every bucket shape, zero recompiles
    with sanitize.recompile_budget(0, what="steady-state serving"):
        row_off = loadgen.run_step(server, "smoke", x[:256], 10,
                                   offered_qps=300.0, duration_s=1.5)
    assert row_off["completed"] > 200 and row_off["errors"] == 0, row_off
    # steady state, tracing ON (events + request contexts + exemplars)
    # with the exposition endpoint scraped MID-load — still zero
    # recompiles: tracing is host-side only
    obs.enable(registry=reg, hbm=False, events=True)
    scrape = {}
    def _scrape():
        # any failure is CAPTURED, not swallowed: a dead scraper thread
        # must surface as the real HTTP/timeout error, not a bare
        # KeyError('metrics') downstream
        try:
            import time as _t
            _t.sleep(0.5)  # land mid-burst
            url = server.expo.url
            scrape["metrics"] = urllib.request.urlopen(
                url + "/metrics", timeout=10).read().decode()
            scrape["healthz"] = json.loads(urllib.request.urlopen(
                url + "/healthz", timeout=10).read())
        except Exception as e:
            scrape["error"] = repr(e)
    scraper = threading.Thread(target=_scrape)
    scraper.start()
    with sanitize.recompile_budget(0, what="traced+scraped serving"):
        row = loadgen.run_step(server, "smoke", x[:256], 10,
                               offered_qps=300.0, duration_s=1.5)
    scraper.join(timeout=15)
    assert "error" not in scrape, f"mid-load scrape failed: {scrape['error']}"
    assert row["completed"] > 200 and row["errors"] == 0, row
    assert row["latency_p99_s"] is not None, row
    # the tracing-overhead bar (ISSUE 15 acceptance): enabled tracing
    # costs <= 5% on the serve p50, with a 0.25 ms absolute floor for
    # CPU-CI scheduler jitter (the p50 itself is ~linger-dominated)
    p50_off, p50_on = row_off["latency_p50_s"], row["latency_p50_s"]
    assert p50_on <= max(p50_off * 1.05, p50_off + 2.5e-4), (
        f"tracing overhead too high: p50 {p50_off*1e3:.3f} ms off -> "
        f"{p50_on*1e3:.3f} ms on")
    # the mid-load scrape parses as Prometheus text format with the
    # serve.* and hbm.* families labeled
    fams = parse_prometheus(scrape["metrics"])
    assert any(f.startswith("raft_tpu_serve_") for f in fams), sorted(fams)
    req = fams.get("raft_tpu_serve_requests")
    assert req and any(s["labels"].get("tenant") == "smoke"
                       for s in req), req
    assert "raft_tpu_hbm_bytes_limit" in fams, sorted(fams)
    lat_series = fams.get("raft_tpu_serve_latency_s")
    assert lat_series and any(s["series"].endswith("_bucket")
                              for s in lat_series), "no histogram buckets"
    assert scrape["healthz"]["tenants"].get("smoke") in (
        "serving", "degraded"), scrape["healthz"]
    # overload: every dispatch stalled 0.2 s -> the bounded queue must
    # shed with the typed queue_full reason, and every accepted request
    # still terminates (run_step waits on all futures)
    faults.install_plan({"faults": [{"site": "serve.dispatch",
                                     "kind": "sleep", "sleep_s": 0.2,
                                     "times": 0}]})
    over = loadgen.run_step(server, "smoke", x[:256], 10,
                            offered_qps=800.0, duration_s=1.0)
    faults.clear_plan()
    assert over["shed"] > 0, over
    assert over["shed_reasons"].get("queue_full", 0) > 0, over
    # chaos: injected OOM mid-batch walks the degrade ladder and the
    # served results are EXACT (identical to the fault-free serve)
    d_c, i_c = server.search("smoke", x[7], 10)
    faults.install_plan({"faults": [{"site": "ivf_pq.search",
                                     "kind": "oom", "times": 1}]})
    d_f, i_f = server.search("smoke", x[7], 10)
    faults.clear_plan()
    np.testing.assert_array_equal(i_f, i_c)
    # exemplar acceptance (ISSUE 15): the p99 resolves to >= 1 concrete
    # trace id, and that request's full timeline renders in
    # obsdump --slowest from a live flight dump (tenant health header
    # included — the registry section rides every dump)
    lat = reg.snapshot()["histograms"]["serve.latency_s"]
    ex = exemplars_for_quantile(lat, 0.99)
    assert ex, "serve.latency_s p99 resolved to no exemplars"
    shutil.rmtree("/tmp/raft_tpu_serve_flight", ignore_errors=True)
    dump_path = flight.dump_now("ci-serve",
                                dump_dir="/tmp/raft_tpu_serve_flight")
    assert dump_path, "flight dump failed"
obs.disable()
p = subprocess.run([sys.executable, "-m", "tools.obsdump", dump_path,
                    "--slowest", "3"], capture_output=True, text=True)
assert p.returncode == 0, p.stderr
assert ex[0]["trace_id"] in p.stdout, (
    f"exemplar {ex[0]['trace_id']} missing from obsdump --slowest:\n"
    + p.stdout)
assert "serve.request" in p.stdout and "serve.dispatch" in p.stdout, \
    p.stdout
assert "tenants: smoke=" in p.stdout, p.stdout  # health header
c = reg.snapshot()["counters"]
assert c.get("serve.requests{tenant=smoke}", 0) > 400, c
assert c.get("serve.shed{reason=queue_full}", 0) > 0, c
assert any(k.startswith("degrade.steps{") and "site=ivf_pq.search" in k
           for k in c), c
assert c.get("serve.registry.admit{tenant=smoke}", 0) == 1, c
h = reg.snapshot()["histograms"]["serve.latency_s"]
print(f"serve smoke OK: {row['completed']} traced requests at "
      f"{row['qps']:.0f} qps (p50 {p50_off*1e3:.2f} -> {p50_on*1e3:.2f} "
      f"ms traced, p99 {row['latency_p99_s']*1e3:.1f} ms, 0 recompiles, "
      f"endpoint scraped mid-load), {over['shed']} shed under stall "
      f"({over['shed_reasons']}), OOM ladder walk exact, "
      f"{len(ex)} p99 exemplars -> obsdump --slowest renders "
      f"{ex[0]['trace_id']}, {h['count']} latency samples")
EOF
# blocking: the committed serving latency-vs-throughput baseline joins
# and passes the benchdiff self-compare (schema/provenance gate — CPU
# qps across machines never gates, same convention as cpu_smoke)
python -m tools.benchdiff serve_cpu_smoke serve_cpu_smoke \
    --md /tmp/raft_tpu_serve_baseline_scoreboard.md | tail -3

echo "== memory-tiered serving smoke (ISSUE 17: host-resident raw vectors"
echo "   with candidate-row prefetch under the scan — host tenant bit-equal"
echo "   to its HBM twin under recompile_budget(0); chaos: HBM pressure"
echo "   demotes raw vectors BEFORE any eviction, /indexz shows raw=host,"
echo "   demoted tenant serves exact, re-promoted when pressure clears) =="
python - <<'EOF'
# Leg 1 — the twins: the same index admitted twice, raw vectors on
# device vs placed on host. The host twin's exact re-rank runs through
# the tiered candidate-row prefetch (pipeline sub-batch pinned to 4 so
# 16-query dispatches split into 4 overlapping stages) and every
# served batch must be BIT-EQUAL to the device twin — under the PR-3
# zero-recompile budget at steady state.
import os
os.environ["RAFT_TPU_TIERED_BATCH"] = "4"

import numpy as np
import jax.numpy as jnp

from raft_tpu import obs, serve
from raft_tpu.obs import sanitize
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.neighbors import ivf_pq
from raft_tpu.serve.dispatch import dispatch_batch

rng = np.random.default_rng(0)
x = rng.random((20_000, 32), dtype=np.float32)
xd = jnp.asarray(x)
idx = ivf_pq.build(xd, ivf_pq.IndexParams(
    n_lists=64, pq_dim=16, seed=0, cache_reconstruction="never"))
params = ivf_pq.SearchParams(n_probes=8, scan_mode="per_query",
                             refine="f32_regen", refine_ratio=4.0,
                             lut_dtype="float32")
reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False)
registry = serve.IndexRegistry(budget_bytes=4 << 30)
registry.admit("hbm_twin", idx, params=params, default_k=10, dataset=xd)
registry.admit("host_twin", idx, params=params, default_k=10,
               dataset=xd, placement=serve.Placement(raw="host"))
assert isinstance(registry.peek("host_twin").dataset, np.ndarray)
# warm the one serving shape, then steady state must not recompile
q0 = jnp.asarray(x[:16])
dispatch_batch(registry.get("hbm_twin"), q0, 10)
dispatch_batch(registry.get("host_twin"), q0, 10)
with sanitize.recompile_budget(0, what="tiered steady-state serving"):
    for a in range(0, 128, 16):
        q = jnp.asarray(x[a:a + 16])
        d_h, i_h = dispatch_batch(registry.get("hbm_twin"), q, 10)
        d_t, i_t = dispatch_batch(registry.get("host_twin"), q, 10)
        np.testing.assert_array_equal(np.asarray(i_t), np.asarray(i_h))
        np.testing.assert_array_equal(np.asarray(d_t), np.asarray(d_h))
c = reg.snapshot()["counters"]
hits = sum(v for k, v in c.items()
           if k.startswith("serve.prefetch.hit") and "host_twin" in k)
stalls = sum(v for k, v in c.items()
             if k.startswith("serve.prefetch.stall") and "host_twin" in k)
assert hits + stalls == 9 * 4, (hits, stalls)  # 9 batches x 4 stages
assert any(k.startswith("refine.dispatch") and "tiered_prefetch" in k
           for k in c), sorted(k for k in c if "refine" in k)

# Leg 2 — chaos: synthetic HBM pressure. Two resident tenants with
# device-resident raw vectors; a third admit that would not fit must
# DEMOTE their raw tiers to host (counted degrade.steps to=demote_raw)
# instead of evicting anyone; /indexz shows raw=host + demoted; the
# demoted twin keeps serving bit-equal; evicting the newcomer
# re-promotes the demoted raw tiers to HBM.
reg2 = MetricsRegistry()
obs.enable(registry=reg2, hbm=False)
pressure = serve.IndexRegistry(budget_bytes=300_000, headroom_frac=0.0)
pressure.admit("t1", object(), dataset=jnp.ones((1000, 32), jnp.float32))
pressure.admit("t2", object(), dataset=jnp.ones((1000, 32), jnp.float32))
pressure.admit("big", object(),
               dataset=jnp.ones((2000, 32), jnp.float32))
c2 = reg2.snapshot()["counters"]
for name in ("t1", "t2"):
    t = pressure.peek(name)
    assert t.state != "evicted" and t.demoted, (name, t.state)
    assert t.placement.raw == "host", t.placement
assert not any(k.startswith("serve.registry.evict") for k in c2), c2
assert sum(v for k, v in c2.items()
           if k.startswith("degrade.steps") and "to=demote_raw" in k) == 2
assert sum(v for k, v in c2.items()
           if k.startswith("serve.registry.demote")) == 2
ten = serve.MicroBatchServer(pressure)._indexz_payload()["tenants"]["t1"]
assert ten["placement"]["raw"] == "host" and ten["demoted"] is True, ten
g2 = reg2.snapshot()["gauges"]
assert g2.get("index.bytes{index=t1,tier=host}") == 128_000, g2
# pressure clears: the evict of the newcomer re-promotes both
pressure.evict("big")
for name in ("t1", "t2"):
    t = pressure.peek(name)
    assert not t.demoted and t.placement.raw == "hbm", (name, t.placement)
assert sum(v for k, v in reg2.snapshot()["counters"].items()
           if k.startswith("serve.registry.promote")) == 2

# the demoted REAL tenant serves bit-equal through dispatch: demote the
# host twin's registry sibling and re-compare one batch
registry.demote_raw("hbm_twin", reason="ci-chaos")
q = jnp.asarray(x[:16])
d_a, i_a = dispatch_batch(registry.get("host_twin"), q, 10)
d_b, i_b = dispatch_batch(registry.get("hbm_twin"), q, 10)
np.testing.assert_array_equal(np.asarray(i_b), np.asarray(i_a))
np.testing.assert_array_equal(np.asarray(d_b), np.asarray(d_a))
obs.disable()
print(f"tiered smoke OK: 9 host-twin batches bit-equal to HBM twin at 0 "
      f"recompiles ({int(hits)} prefetch hits / {int(stalls)} stalls), "
      f"pressure demoted 2 raw tiers before any eviction (/indexz "
      f"raw=host), re-promoted on clear, demoted tenant serves exact")
EOF

echo "== quality plane (ISSUE 16: online recall verifier overhead gate,"
echo "   recall-fault chaos -> floor breach -> quality-gated ladder ->"
echo "   recovery, /indexz + obsdump index-health introspection) =="
python - <<'EOF'
# Part 1 — verifier overhead gate: the shadow verifier (sampled replay
# on a background thread) must not move the serving p50 by more than
# the documented bar (5% or 0.25 ms, whichever is larger) — the same
# bar the tracing-overhead gate uses.
import json, shutil, subprocess, sys, time, urllib.request
import numpy as np
import jax.numpy as jnp

from raft_tpu import obs, serve
from raft_tpu.obs import flight
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.neighbors import ivf_flat, ivf_pq
from raft_tpu.robust import faults
from raft_tpu.serve import loadgen
from raft_tpu.serve.errors import ShedError

rng = np.random.default_rng(0)
x = rng.random((8_000, 32), dtype=np.float32)
flat = ivf_flat.build(jnp.asarray(x), ivf_flat.IndexParams(n_lists=16))
rows = {}
for verify in (0.0, 0.25):
    reg = MetricsRegistry()
    obs.enable(registry=reg, hbm=False)
    registry = serve.IndexRegistry(budget_bytes=2 << 30)
    registry.admit("t", flat, params=ivf_flat.SearchParams(n_probes=8),
                   default_k=10, dataset=x)
    server = serve.MicroBatchServer(registry, serve.ServerConfig(
        max_batch=16, linger_s=0.002, verify_sample=verify,
        verify_rate_per_s=50.0))
    with server:
        # offered load well under the CPU backend's capacity: p50 then
        # measures service latency, not queue depth — the verifier's
        # background replay must not move it
        loadgen.run_step(server, "t", x[:256], 10,
                         offered_qps=50.0, duration_s=0.4)  # warm
        rows[verify] = loadgen.run_step(server, "t", x[:256], 10,
                                        offered_qps=50.0,
                                        duration_s=1.5)
        if verify:
            assert server.verifier is not None
            assert server.verifier.state()["verified_total"] > 0, \
                "verifier sampled nothing during the on-step"
    obs.disable()
p50_off = rows[0.0]["latency_p50_s"]
p50_on = rows[0.25]["latency_p50_s"]
assert p50_on <= max(p50_off * 1.05, p50_off + 2.5e-4), (
    f"verifier overhead too high: p50 {p50_off*1e3:.3f} ms off -> "
    f"{p50_on*1e3:.3f} ms on")
print(f"verifier overhead OK: p50 {p50_off*1e3:.3f} -> "
      f"{p50_on*1e3:.3f} ms with shadow verification on")

# Part 2 — recall-fault chaos: clustered vectors make the fp8 LUT rung
# genuinely lossy (~0.9 -> ~0.2 recall@10 measured on this config), so
# forcing the ladder onto fp8 via injected OOMs while the verifier
# samples every request drives the measured recall below the tenant's
# floor: the monitor must breach (healthz "degraded"), arm the quality
# gate (faulted requests now SHED instead of serving fp8 answers,
# counted degrade.refused{reason=recall_floor}), and recover once the
# faults stop and fresh verdicts refill the window.
xc = (rng.normal(0, 0.02, (4_000, 64)) +
      rng.random((40, 64))[rng.integers(0, 40, 4_000)]).astype(np.float32)
pq = ivf_pq.build(jnp.asarray(xc), ivf_pq.IndexParams(
    n_lists=16, pq_dim=64, seed=0, cache_reconstruction="never"))
reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False, events=True)
registry = serve.IndexRegistry(budget_bytes=2 << 30)
registry.admit("acme", pq, params=ivf_pq.SearchParams(
    n_probes=16, lut_dtype="float32", scan_mode="per_query"),
    default_k=10, dataset=xc, recall_floor=0.6)
# a deliberately skewed flat tenant rides along for the /indexz smoke
skew = (np.concatenate([rng.normal(0.5, 0.01, (1_800, 64)),
                        rng.random((200, 64))])).astype(np.float32)
registry.admit("skewed", ivf_flat.build(
    jnp.asarray(skew), ivf_flat.IndexParams(n_lists=16)),
    params=ivf_flat.SearchParams(n_probes=8), default_k=10,
    dataset=skew)
server = serve.MicroBatchServer(registry, serve.ServerConfig(
    max_batch=4, linger_s=0.001, verify_sample=1.0,
    verify_rate_per_s=1e9, expo_port=0))

OOM2 = {"faults": [{"site": "ivf_pq.search", "kind": "oom",
                    "times": 2}]}


def healthz(url):
    return json.loads(urllib.request.urlopen(
        url + "/healthz", timeout=10).read())


def wait(pred, what, timeout=60.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


with server:
    url = server.expo.url
    # healthy phase: self-queries over the admitted dataset at
    # exhaustive n_probes -> near-perfect verified recall
    for j in range(16):
        server.search("acme", xc[j], 10)
    wait(lambda: reg.snapshot()["gauges"].get(
        "quality.samples{k=10,tenant=acme}", 0) >= 8,
        "healthy recall verdicts")
    g = reg.snapshot()["gauges"]
    assert g["quality.recall{k=10,tenant=acme}"] > 0.8, g
    assert healthz(url)["status"] == "ok"
    # fault phase: every request OOMs twice -> ladder lands on fp8_lut
    # -> verifier scores the served (lossy) ids against exact truth.
    # The breach trips on the WILSON LOWER BOUND crossing the floor —
    # and once it trips, the gate sheds further faulted requests, so
    # bad verdicts stop arriving and the point estimate freezes; the
    # assertable signal is the bound, not the mean.
    for j in range(150):
        faults.install_plan(OOM2)
        try:
            server.search("acme", xc[j % 1000], 10)
        except ShedError:
            pass  # gate may already be up mid-loop
        if server.slo.breached():
            break
    faults.clear_plan()
    wait(lambda: server.slo.breached() == ["acme"], "recall-floor breach")
    g = reg.snapshot()["gauges"]
    assert g["quality.recall_ci_low{k=10,tenant=acme}"] < 0.6, g
    assert g["slo.recall_floor_ok{tenant=acme}"] == 0.0, g
    doc = healthz(url)
    assert doc["status"] == "degraded", doc
    assert doc["slo"]["recall_floor_breached"] == ["acme"], doc
    c = reg.snapshot()["counters"]
    assert c.get("slo.recall_floor_breach{tenant=acme}", 0) >= 1, c
    # gate phase: with the breach armed, a faulted request must SHED
    # (quality rungs refused; ladder exhausts) instead of serving fp8
    shed = 0
    for _ in range(3):
        faults.install_plan(OOM2)
        try:
            server.search("acme", xc[0], 10)
        except ShedError as e:
            shed += 1
            assert "overload" in str(e), e
    faults.clear_plan()
    assert shed == 3, f"gated+faulted requests served anyway ({shed}/3)"
    c = reg.snapshot()["counters"]
    for rung in ("bf16_lut", "fp8_lut", "decline_fused"):
        key = f"degrade.refused{{reason=recall_floor,rung={rung}}}"
        assert c.get(key, 0) >= 3, (key, c)
    assert c.get("serve.shed{reason=overload}", 0) >= 3, c
    # recovery phase: clean traffic refills the verdict window with
    # good recall -> the monitor promotes the tenant back
    for j in range(220):
        server.search("acme", xc[j % 1000], 10)
        if not server.slo.breached():
            break
    wait(lambda: not server.slo.breached(), "recall-floor recovery",
         timeout=90.0)
    c = reg.snapshot()["counters"]
    assert c.get("slo.recall_floor_recovered{tenant=acme}", 0) >= 1, c
    doc = healthz(url)
    assert doc["status"] == "ok", doc
    # Part 3 — introspection: /indexz serves live per-tenant index
    # health (the skewed tenant shows its skew), and the flight dump's
    # quality section + index gauges render through obsdump
    idxz = json.loads(urllib.request.urlopen(
        url + "/indexz", timeout=30).read())
    sk = idxz["tenants"]["skewed"]["stats"]["lists"]
    assert sk["n_lists"] == 16 and sk["cv"] > 0.5, sk
    assert idxz["tenants"]["acme"]["recall_floor"] == 0.6, idxz
    assert idxz["tenants"]["acme"]["stats"]["pq"]["rel_error"] > 0, idxz
    shutil.rmtree("/tmp/raft_tpu_quality_flight", ignore_errors=True)
    dump_path = flight.dump_now("ci-quality",
                                dump_dir="/tmp/raft_tpu_quality_flight")
    assert dump_path, "flight dump failed"
obs.disable()
p = subprocess.run([sys.executable, "-m", "tools.obsdump", dump_path,
                    "--worst-recall", "2"], capture_output=True,
                   text=True)
assert p.returncode == 0, p.stderr
assert "quality:" in p.stdout, p.stdout            # flight header
assert "index health" in p.stdout, p.stdout        # introspection table
assert "recall verdicts" in p.stdout, p.stdout     # drill-down section
assert "serve.request" in p.stdout, p.stdout       # resolved timeline
print("quality chaos OK: breach -> degraded healthz -> "
      f"{int(c['degrade.refused{reason=recall_floor,rung=fp8_lut}'])} "
      "refused fp8 rungs -> shed -> recovery; /indexz cv "
      f"{sk['cv']:.2f} on the skewed tenant; obsdump renders the "
      "quality header, index-health table and worst-recall timelines")
EOF

echo "== fleet router smoke (ISSUE 19: two simulated pods on 4-dev halves,"
echo "   PR-15 straggler feed -> typed steer counter, ONE Deadline across"
echo "   the pod hop, DCN-hop pod kill mid-storm -> degraded-but-correct"
echo "   answers with exact failover accounting) =="
python - <<'EOF'
import numpy as np
import jax
import jax.numpy as jnp

from raft_tpu import obs, serve
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.parallel import make_mesh, sharded_knn
from raft_tpu.robust import faults, retry

devs = jax.devices()
assert len(devs) >= 8, devs
rng = np.random.default_rng(0)
x = jnp.asarray(rng.random((2048, 32), dtype=np.float32))
queries = np.asarray(rng.random((16, 32)), np.float32)
k = 5

seen_deadlines = []

def pod_fn(mesh):
    def fn(tenant, q, k_, deadline):
        seen_deadlines.append(deadline)
        v, i = sharded_knn(x, jnp.asarray(q), k_, mesh)
        return np.asarray(v), np.asarray(i)
    return fn

mesh_a = make_mesh(devices=devs[:4])
mesh_b = make_mesh(devices=devs[4:8])
ref_v, ref_i = pod_fn(mesh_a)("t", queries, k, None)
seen_deadlines.clear()

reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False)
router = serve.FleetRouter([
    serve.Pod("a", hosts=("hostA",), dispatch_fn=pod_fn(mesh_a)),
    serve.Pod("b", hosts=("hostB",), dispatch_fn=pod_fn(mesh_b))])
serve.set_router(router)

# the ONE Deadline object crosses the pod hop untouched
dl = retry.Deadline(30.0)
router.dispatch("t", queries, k, deadline=dl)
assert seen_deadlines[-1] is dl

# PR-15 straggler-table feed -> steering, visible as a typed counter
assert router.note_stragglers([
    {"collective": "comms.ring_topk", "slowest": "hostB",
     "skew_frac": 0.42}]) == 1
for _ in range(4):
    router.dispatch("t", queries, k)
c = reg.snapshot()["counters"]
assert c["serve.router.steer{away_from=hostB,reason=straggler}"] >= 1, c
assert c["serve.router.straggler{host=hostB}"] == 1.0, c

# chaos: pod b's DCN hop dies mid-storm; every answer stays correct
faults.install_plan({"faults": [
    {"site": "serve.router.hop.b", "kind": "error", "after": 1,
     "times": 0}]})
try:
    router2 = serve.FleetRouter([
        serve.Pod("a", hosts=("hostA",), dispatch_fn=pod_fn(mesh_a)),
        serve.Pod("b", hosts=("hostB",), dispatch_fn=pod_fn(mesh_b))])
    answers = [router2.dispatch("t", queries, k) for _ in range(6)]
finally:
    faults.clear_plan()
for v, i in answers:
    assert np.array_equal(i, ref_i)
    np.testing.assert_allclose(v, ref_v, rtol=1e-5)
c = reg.snapshot()["counters"]
assert c["serve.router.pod_down{pod=b}"] == 1.0, c
assert c["serve.router.degraded{reason=pod_lost}"] == 1.0, c
assert not router2.pods[1].healthy
serve.clear_router(router)
obs.disable()
print("fleet router OK: steered away from hostB, one Deadline across "
      "the hop, pod b killed mid-storm ->",
      len(answers), "degraded-but-correct answers")
EOF

echo "== cost & capacity plane smoke (ISSUE 20: 3-tenant skewed load under"
echo "   recompile_budget(0) — per-tenant device_s ordering matches the"
echo "   offered-load ordering, conservation within 5%, ledger-overhead"
echo "   gate, mid-load /costz + cost_* scrape, synthetic resident-bytes"
echo "   ramp trips capacity.alert + ONE preemptive raw-tier demotion"
echo "   BEFORE any pressure cliff, killed run's flight dump renders the"
echo "   cost section via obsdump --cost) =="
python - <<'EOF'
# one server, one index, THREE tenants driven at skewed offered loads
# (300/100/30 qps): the ledger must rank their device_s the same way
# the offered load ranks them, conserve attributed time against its
# own measured batch wall, and cost <= the documented bar when on
import json, shutil, subprocess, sys, threading, urllib.request
import numpy as np
import jax.numpy as jnp

from raft_tpu import obs, serve
from raft_tpu.obs import capacity as _capacity
from raft_tpu.obs import cost as _cost
from raft_tpu.obs import flight, sanitize
from raft_tpu.obs.expo import parse_prometheus
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.neighbors import ivf_pq
from raft_tpu.serve import loadgen

rng = np.random.default_rng(0)
x = rng.random((20_000, 32), dtype=np.float32)
xd = jnp.asarray(x)
idx = ivf_pq.build(xd, ivf_pq.IndexParams(
    n_lists=64, pq_dim=16, seed=0, cache_reconstruction="never"))
reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False)
registry = serve.IndexRegistry(budget_bytes=4 << 30)
params = ivf_pq.SearchParams(n_probes=8, scan_mode="per_query")
for name in ("heavy", "mid", "light"):
    registry.admit(name, idx, params=params, default_k=10)
# a cold demotable tenant (device-resident raw vectors) for the
# forecast leg below — never dispatched, so it is the coldest LRU
registry.admit("demotable", idx, params=params, default_k=10,
               dataset=xd)
server = serve.MicroBatchServer(registry, serve.ServerConfig(
    max_batch=16, queue_depth=128, linger_s=0.002, default_slo_s=1.0,
    expo_port=0))
with server:
    for j in range(5):
        server.search("heavy", x[j], 10)
    assert _cost.get_ledger() is server.ledger is not None
    assert _capacity.get_model() is server.capacity is not None
    # ledger-overhead gate (the ISSUE 20 acceptance bar): the same
    # burst with the ledger uninstalled vs installed, obs on for both
    # so the delta isolates the ledger's dispatch tap + bookkeeping
    _cost.clear_ledger(server.ledger)
    with sanitize.recompile_budget(0, what="serving, ledger off"):
        row_off = loadgen.run_step(server, "heavy", x[:256], 10,
                                   offered_qps=300.0, duration_s=1.5)
    assert row_off["device_s"] is None, row_off   # no ledger, no column
    _cost.set_ledger(server.ledger)
    # the skewed 3-tenant load, ledger ON, still zero recompiles; the
    # heavy step is scraped MID-load (/costz + /metrics)
    scrape = {}
    def _scrape():
        try:
            import time as _t
            _t.sleep(0.5)
            url = server.expo.url
            scrape["costz"] = json.loads(urllib.request.urlopen(
                url + "/costz", timeout=10).read())
            scrape["metrics"] = urllib.request.urlopen(
                url + "/metrics", timeout=10).read().decode()
        except Exception as e:
            scrape["error"] = repr(e)
    scraper = threading.Thread(target=_scrape)
    scraper.start()
    rows = {}
    with sanitize.recompile_budget(0, what="serving, ledger on"):
        for tenant, qps in (("heavy", 300.0), ("mid", 100.0),
                            ("light", 30.0)):
            rows[tenant] = loadgen.run_step(server, tenant, x[:256], 10,
                                            offered_qps=qps,
                                            duration_s=1.5)
    scraper.join(timeout=15)
    assert "error" not in scrape, f"mid-load scrape failed: {scrape['error']}"
    for tenant, r in rows.items():
        assert r["errors"] == 0, (tenant, r)
        assert r["device_s"] is not None and r["device_s"] > 0, (tenant, r)
    # the ledger-overhead bar: <= 5% on the serve p50 with the 0.25 ms
    # absolute floor for CPU-CI scheduler jitter
    p50_off, p50_on = row_off["latency_p50_s"], rows["heavy"]["latency_p50_s"]
    assert p50_on <= max(p50_off * 1.05, p50_off + 2.5e-4), (
        f"ledger overhead too high: p50 {p50_off*1e3:.3f} ms off -> "
        f"{p50_on*1e3:.3f} ms on")
    # attribution ordering matches the offered-load ordering
    dev = server.ledger.device_seconds()
    assert dev["heavy"] > dev["mid"] > dev["light"] > 0, dev
    shares = server.ledger.shares()
    assert shares["heavy"] > shares["mid"] > shares["light"], shares
    # conservation: sum of per-tenant attribution == measured batch
    # wall, within the 5% epsilon (equality holds by construction; the
    # epsilon absorbs float noise only)
    cons = server.ledger.conservation()
    assert cons["batch_wall_s"] > 0, cons
    assert cons["rel_err"] <= 0.05, cons
    # the mid-load /costz carries both halves of the plane
    ledger_doc = scrape["costz"]["ledger"]
    assert set(("heavy", "mid", "light")) <= set(ledger_doc["tenants"]), \
        sorted(ledger_doc["tenants"])
    assert "conservation" in ledger_doc, sorted(ledger_doc)
    assert "headroom_frac" in scrape["costz"]["capacity"], scrape["costz"]
    # and the cost_* families parse off the mid-load /metrics scrape,
    # the process_* self-telemetry beside them
    fams = parse_prometheus(scrape["metrics"])
    assert "raft_tpu_cost_device_s" in fams, sorted(fams)
    assert "raft_tpu_cost_share" in fams, sorted(fams)
    assert any(s["labels"].get("tenant") == "heavy"
               for s in fams["raft_tpu_cost_device_s"]), fams
    for f in ("process_cpu_seconds_total",
              "process_resident_memory_bytes", "process_open_fds"):
        assert f in fams, sorted(fams)
    # the forecast loop: a synthetic resident-bytes ramp (injected
    # clock, 3 ticks climbing 86% -> 90% of the registry's own usable
    # budget) trips capacity.alert AND the next admission preemptively
    # demotes the cold tenant's raw tier — while actual pressure is
    # nowhere near the cliff (the admission fits outright; nothing is
    # evicted)
    usable = float(registry.usable_bytes)
    clk = {"t": 0.0}
    lvl = {"v": 0.0}
    synth = _capacity.CapacityModel(
        resident_bytes=lambda: lvl["v"],
        usable_bytes=lambda: usable,
        clock=lambda: clk["t"])
    for t, frac in ((0.0, 0.86), (10.0, 0.88), (20.0, 0.90)):
        clk["t"], lvl["v"] = t, usable * frac
        synth.tick()
    c = reg.snapshot()["counters"]
    assert c.get("capacity.alert{resource=hbm}", 0) > 0, c
    _capacity.set_model(synth)
    registry.admit("trigger", object(), size_bytes=100, default_k=10)
    _capacity.set_model(server.capacity)
    c = reg.snapshot()["counters"]
    assert c.get("serve.registry.preemptive_demote{tenant=demotable}",
                 0) == 1.0, c
    demoted = registry.peek("demotable")
    assert demoted.demoted, "raw tier did not move"
    assert demoted.state not in ("evicted", "failed"), demoted.state
    assert "serve.registry.evict{tenant=demotable,reason=pressure}" \
        not in c, c  # demoted BEFORE any cliff, never evicted
    # the killed run's story: a flight dump taken now carries the
    # "cost" section and obsdump --cost renders the attribution table
    shutil.rmtree("/tmp/raft_tpu_cost_flight", ignore_errors=True)
    dump_path = flight.dump_now("ci-cost",
                                dump_dir="/tmp/raft_tpu_cost_flight")
    assert dump_path, "flight dump failed"
    raw = json.load(open(dump_path))
    assert "cost" in raw, sorted(raw)
    assert raw["cost"]["ledger"]["tenants"], raw["cost"]
obs.disable()
p = subprocess.run([sys.executable, "-m", "tools.obsdump", dump_path,
                    "--cost"], capture_output=True, text=True)
assert p.returncode == 0, p.stderr
assert "cost & capacity" in p.stdout, p.stdout
assert "conservation:" in p.stdout, p.stdout
for tenant in ("heavy", "mid", "light"):
    assert tenant in p.stdout, p.stdout
print(f"cost plane OK: device_s heavy {dev['heavy']:.3f} > mid "
      f"{dev['mid']:.3f} > light {dev['light']:.3f} s (shares "
      f"{shares['heavy']:.2f}/{shares['mid']:.2f}/"
      f"{shares['light']:.2f}), conservation rel_err "
      f"{cons['rel_err']:.1e}, ledger p50 {p50_off*1e3:.2f} -> "
      f"{p50_on*1e3:.2f} ms, /costz + cost_* scraped mid-load, ramp "
      f"-> capacity.alert + 1 preemptive demote, obsdump --cost renders")
EOF

echo "== trace export round-trip (instrumented search -> Perfetto JSON) =="
python - <<'EOF'
import json
import numpy as np
import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.obs import trace
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.neighbors import ivf_pq

rng = np.random.default_rng(0)
x = jnp.asarray(rng.random((3000, 32), dtype=np.float32))
obs.enable(sync=True, registry=MetricsRegistry(), events=True)
try:
    idx = ivf_pq.build(x, ivf_pq.IndexParams(
        n_lists=16, pq_dim=16, seed=0, cache_reconstruction="never"))
    ivf_pq.search(idx, x[:64], 10,
                  ivf_pq.SearchParams(n_probes=8, scan_mode="per_query"))
finally:
    obs.disable()
n = trace.export_chrome("/tmp/raft_tpu_ci_trace.json")
assert n >= 4, f"expected build stage spans in the trace, got {n} events"
with open("/tmp/raft_tpu_ci_trace.json") as f:
    doc = json.load(f)
names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
need = {"ivf_pq.search", "ivf_pq.build", "ivf_pq.build.train",
        "ivf_pq.build.encode"}
assert need <= names, f"missing spans in trace: {sorted(need - names)}"
assert all("ts" in e and "dur" in e and "tid" in e
           for e in doc["traceEvents"] if e["ph"] == "X")
print(f"trace round-trip OK: {n} events, spans {sorted(names)}")
EOF
python -m tools.obsdump /tmp/raft_tpu_ci_trace.json | grep -q "ivf_pq.search" \
  || { echo "obsdump failed to render the trace"; exit 1; }
echo "obsdump render OK"

echo "== flight recorder smoke (simulated SIGTERM mid-run) =="
python - <<'EOF'
import json, os, signal, subprocess, sys, time

DUMP_DIR = "/tmp/raft_tpu_ci_flight"
subprocess.run(["rm", "-rf", DUMP_DIR])
# child: an instrumented loop with the recorder armed; parent SIGTERMs
# it mid-run and the dump must survive, parseable, with spans inside
code = """
import time
from raft_tpu import obs
from raft_tpu.obs import flight
from raft_tpu.core import tracing

# every_s=0: an inherited RAFT_TPU_FLIGHT_EVERY_S would add periodic
# _latest.json checkpoints and make the dump selection ambiguous
flight.install(%r, every_s=0)
obs.enable(events=True, hbm=False)
print("armed", flush=True)
while True:
    with tracing.span("ci.loop"):
        time.sleep(0.01)
""" % DUMP_DIR
p = subprocess.Popen([sys.executable, "-c", code],
                     stdout=subprocess.PIPE, text=True)
assert p.stdout.readline().strip() == "armed"
time.sleep(0.5)  # a few loop spans into the ring
p.send_signal(signal.SIGTERM)
p.wait(timeout=30)
docs = []
for f in sorted(os.listdir(DUMP_DIR)):
    if f.startswith("flight_") and f.endswith(".json"):
        with open(os.path.join(DUMP_DIR, f)) as fh:
            docs.append((f, json.load(fh)))
dumps = [f for f, d in docs if d["reason"].startswith("signal")]
assert dumps, f"SIGTERM'd child left no signal dump: {[f for f, _ in docs]}"
doc = dict(docs)[dumps[0]]
assert any(e["name"] == "ci.loop" for e in doc["events"]), \
    "flight dump lost the event ring"
assert "span.ci.loop" in doc["metrics"]["histograms"]
print(f"flight SIGTERM smoke OK: {sorted(dumps)[0]}, "
      f"{len(doc['events'])} events, {len(doc['logs'])} log lines")
EOF

echo "== Pallas LUT-scan tier smoke (interpret mode, TPU-shaped dispatch) =="
RAFT_TPU_PALLAS_LUTSCAN=always python - <<'EOF'
import numpy as np
import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.neighbors import ivf_pq

rng = np.random.default_rng(0)
x = jnp.asarray(rng.random((3000, 32), dtype=np.float32))
idx = ivf_pq.build(x, ivf_pq.IndexParams(
    n_lists=16, pq_dim=16, seed=0, cache_reconstruction="never"))
reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False)
try:
    # oversampled (k_cand >= 400) approx search: must auto-upgrade to
    # the fused LUT kernel and record span.ivf_pq.search.scan
    ivf_pq.search(idx, x[:64], 400, ivf_pq.SearchParams(
        n_probes=8, scan_mode="grouped", scan_select="approx"))
finally:
    obs.disable()
snap = reg.snapshot()
c = snap["counters"].get("ivf_pq.scan.dispatch{impl=pallas_lut}", 0)
assert c >= 1, snap["counters"]
scan_span = snap["histograms"].get("span.ivf_pq.search.scan")
assert scan_span and scan_span["count"] >= 1, snap["histograms"].keys()
# ISSUE 12: the SAME eligible shape with a filter_bitset stays on the
# tier — the kernel streams the packed keep bits; the dispatch counts
# filtered=1 and the retired filter_bitset fallback reason stays ZERO
from raft_tpu.core import bitset

mask = np.ones(3000, bool)
mask[::3] = False
bits = bitset.from_mask(jnp.asarray(mask))
reg2 = MetricsRegistry()
obs.enable(registry=reg2, hbm=False)
try:
    _, ids = ivf_pq.search(idx, x[:64], 400, ivf_pq.SearchParams(
        n_probes=8, scan_mode="grouped", scan_select="approx"),
        filter_bitset=bits)
finally:
    obs.disable()
c2 = reg2.snapshot()["counters"]
assert c2.get("ivf_pq.scan.dispatch{filtered=1,impl=pallas_lut}",
              0) >= 1, c2
assert c2.get("ivf_pq.scan.fallback{reason=filter_bitset}", 0) == 0, c2
got = np.asarray(ids)
got = got[got >= 0]
assert got.size and not (got % 3 == 0).any(), \
    "filtered ids leaked through the fused scan"
print("pallas LUT-scan smoke OK: dispatch counter + scan span recorded; "
      "filtered dispatch stays on the tier (filter_bitset fallback = 0)")
EOF

echo "== Pallas gather-refine tier smoke (interpret mode, streamed refine) =="
RAFT_TPU_PALLAS_REFINE=always python - <<'EOF'
import numpy as np
import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.obs.metrics import MetricsRegistry
from raft_tpu.neighbors import refine

rng = np.random.default_rng(0)
x = jnp.asarray(rng.random((2000, 32), dtype=np.float32))
q = jnp.asarray(rng.random((32, 32), dtype=np.float32))
cand = jnp.asarray(rng.integers(0, 2000, (32, 400)).astype(np.int32))
reg = MetricsRegistry()
obs.enable(registry=reg, hbm=False)
try:
    d_p, i_p = refine.refine(x, q, cand, 10)
finally:
    obs.disable()
d_x, i_x = refine._refine_impl(x, q, cand, 10, "sqeuclidean")
np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_x),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_array_equal(np.asarray(i_p), np.asarray(i_x))
snap = reg.snapshot()
c = snap["counters"].get("refine.dispatch{impl=pallas_gather}", 0)
assert c >= 1, snap["counters"]
assert "span.refine.fused_scan" in snap["histograms"], \
    snap["histograms"].keys()
print("gather-refine smoke OK: fused tier parity + dispatch counter "
      "+ span recorded")
EOF

echo "== CI artifacts =="
# one directory a CI system (or a human triaging a red run) picks up
# whole: the graftlint findings, the obs-smoke bench record (with cost
# columns + env provenance), and the benchdiff scoreboard + verdict
ARTIFACTS="${RAFT_TPU_CI_ARTIFACTS:-/tmp/raft_tpu_ci_artifacts}"
mkdir -p "$ARTIFACTS"
cp /tmp/graftlint_report.json \
   /tmp/capacity_prove_report.json \
   /tmp/raft_tpu_obs_bench.json \
   /tmp/raft_tpu_benchdiff_scoreboard.md \
   /tmp/raft_tpu_build_baseline_scoreboard.md \
   /tmp/raft_tpu_serve_baseline_scoreboard.md \
   /tmp/raft_tpu_benchdiff_verdict.json "$ARTIFACTS"/
ls -l "$ARTIFACTS"
echo "CI artifacts under $ARTIFACTS"

echo "CI: all green"
