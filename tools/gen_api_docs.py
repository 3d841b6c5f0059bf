"""Generate docs/api_reference.md from the package's public surface.

One line per public function/class (first docstring sentence), grouped
by module — the counterpart of the reference's generated API rst trees
(docs/source/cpp_api/, pylibraft_api/). Re-run after adding a module:

    JAX_PLATFORMS=cpu python tools/gen_api_docs.py
"""
import importlib
import inspect
import io
import sys

sys.path.insert(0, "/root/repo")

MODULES = [
    "raft_tpu.core.resources", "raft_tpu.core.errors",
    "raft_tpu.core.logging", "raft_tpu.core.tracing",
    "raft_tpu.core.bitset", "raft_tpu.core.interruptible",
    "raft_tpu.core.serialize", "raft_tpu.core.ids",
    "raft_tpu.obs.metrics", "raft_tpu.obs.spans", "raft_tpu.obs.hbm",
    "raft_tpu.obs.prof",
    "raft_tpu.obs.trace", "raft_tpu.obs.flight", "raft_tpu.obs.expo",
    "raft_tpu.obs.fleet", "raft_tpu.obs.sanitize",
    "raft_tpu.obs.quality", "raft_tpu.obs.index_stats",
    "raft_tpu.obs.cost", "raft_tpu.obs.capacity",
    "raft_tpu.robust.faults", "raft_tpu.robust.retry",
    "raft_tpu.robust.degrade", "raft_tpu.robust.checkpoint",
    "raft_tpu.linalg.blas", "raft_tpu.linalg.solvers",
    "raft_tpu.linalg.eltwise", "raft_tpu.linalg.map_reduce",
    "raft_tpu.matrix.select_k", "raft_tpu.matrix.ops",
    "raft_tpu.random.rng", "raft_tpu.random.generators",
    "raft_tpu.distance.types", "raft_tpu.distance.pairwise",
    "raft_tpu.distance.fused_l2_nn", "raft_tpu.distance.kernels",
    "raft_tpu.sparse.types", "raft_tpu.sparse.ops",
    "raft_tpu.sparse.linalg", "raft_tpu.sparse.distance",
    "raft_tpu.sparse.neighbors", "raft_tpu.sparse.solver",
    "raft_tpu.cluster.kmeans", "raft_tpu.cluster.kmeans_balanced",
    "raft_tpu.cluster.single_linkage", "raft_tpu.cluster.distributed",
    "raft_tpu.label.classlabels",
    "raft_tpu.neighbors.brute_force", "raft_tpu.neighbors.ivf_flat",
    "raft_tpu.neighbors.ivf_pq", "raft_tpu.neighbors.cagra",
    "raft_tpu.neighbors.nn_descent", "raft_tpu.neighbors.refine",
    "raft_tpu.neighbors.tiered",
    "raft_tpu.neighbors.ball_cover",
    "raft_tpu.neighbors.epsilon_neighborhood",
    "raft_tpu.neighbors.sample_filter",
    "raft_tpu.stats.descriptive", "raft_tpu.stats.metrics",
    "raft_tpu.spectral.partition", "raft_tpu.solver.lap",
    "raft_tpu.parallel.mesh", "raft_tpu.parallel.comms",
    "raft_tpu.parallel.merge",
    "raft_tpu.parallel.knn", "raft_tpu.parallel.ivf",
    "raft_tpu.parallel.build",
    "raft_tpu.serve.server", "raft_tpu.serve.registry",
    "raft_tpu.serve.placement",
    "raft_tpu.serve.dispatch", "raft_tpu.serve.loadgen",
    "raft_tpu.serve.slo", "raft_tpu.serve.router",
    "raft_tpu.serve.errors",
    "raft_tpu.ops.pallas_kernels", "raft_tpu.native",
    "raft_tpu.bench.dataset", "raft_tpu.bench.runner",
    "raft_tpu.bench.ingest", "raft_tpu.bench.plot",
    "raft_tpu.bench.prims",
    "tools.benchdiff",
]


# Hand-authored notes appended after a module's generated listing —
# survive regeneration because they live HERE, not in the output file.
NOTES = {
    "raft_tpu.obs.prof": """\
### Device peak table (roofline ceilings)

| kind | peak flops (dense bf16) | HBM bandwidth | ridge (flops/B) |
|---|---|---|---|
| v4 | 275 TF/s | 1228 GB/s | ~224 |
| v5e | 197 TF/s | 819 GB/s | ~241 |
| v5p | 459 TF/s | 2765 GB/s | ~166 |
| cpu | 50 GF/s (PLACEHOLDER) | 20 GB/s (PLACEHOLDER) | 2.5 |

Unknown device kinds degrade to the CPU placeholder; the roofline
classification still runs, its ceiling is just not calibrated. The
flops/bytes inputs are XLA's *static* cost model for the compiled
program (algorithmic flops, estimated post-fusion HBM traffic) —
achieved fractions compare a measured wall time against these
ceilings. See docs/observability.md "Cost attribution & regression
gate".
""",
    "tools.benchdiff": """\
The regression-gate CLI: exit 0 pass / 1 regression / 2 refused
(environment mismatch or nothing joinable). Committed baselines live
under `raft_tpu/bench/baselines/` and resolve by bare name. See
docs/observability.md "Cost attribution & regression gate" for the
noise model and CI wiring.
""",
    "raft_tpu.parallel.build": """\
### Distributed-build decision summary

`ivf_pq.build_distributed` / `ivf_flat.build_distributed` (ISSUE 13)
route here. The choices that matter:

| knob | values | effect |
|---|---|---|
| `coarse` | `"replicated"` (default) \\| `"distributed"` | replicated = the exact single-host trainer over the exact single-host trainset sample (allgatherv'd from the shards) — `assemble_ivf_pq/_ivf_flat` is then BIT-IDENTICAL to `build_chunked`/`build`; distributed = `cluster.distributed.fit`'s psum Lloyd over the *sharded* sample (scales past a replicable trainset, parity waived) |
| `prefetch` | `True` (default) \\| `False` | double-buffered host→HBM prefetcher (chunk N+1's read + `device_put` under chunk N's encode; `build.prefetch.{hit,stall}` counters, `span.*.h2d` = un-hidden wait) vs the serialized copy-then-encode walk (the bench comparison leg) |
| `checkpoint_dir` / `resume` | path, `False`\\|`True`\\|`"auto"` | per-shard preemption safety: shard-axis manifest + per-(shard, chunk) encoded shards; resume replays to a sha-identical sharded index (fingerprints computed once, `fingerprint_s` stamped) |

Comms: one allgatherv of trainset rows (train phase) + one allgatherv
of per-list counts — codes/ids/norms never cross the interconnect.
Output: a `ShardedIvfPq`/`ShardedIvfFlat` (global ids = `rank ·
shard_rows + local` via `core.ids`; `global_list_cap` stamped for
assembly) that `search_ivf_pq`/`search_ivf_flat` consume directly.
""",
    "raft_tpu.serve.server": """\
### Serving decision summary

The request path (ISSUE 14; docs/developer_guide.md "Serving" has the
full policy):

| stage | policy | refusal / signal |
|---|---|---|
| `submit()` | bounded queue keyed `(tenant, k)`; the request's `Deadline(slo_s)` starts here | `ShedError(queue_full\\|not_running)`, `TenantUnknown`; `serve.requests{tenant=}` |
| batcher | drain ≤ `max_batch` within `linger_s`, pad to the next power-of-two bucket (`bucket_sizes`) | queue-expired budgets shed (`reason=deadline`) without chip work; `serve.batch_fill` |
| dispatch | `dispatch_batch` → tenant's `search_resilient` under the group deadline + `DISPATCH_RETRY_POLICY`; the PR-7 ladder is the overload path | `ShedError(overload)` on ladder exhaustion; ladder moves mark the tenant `degraded` |
| completion | per-request slicing, latency into `serve.latency_s` (the p50/p99 source) | late-but-correct results delivered + `serve.deadline_missed` |

Steady state after `start(warmup=True)` holds `recompile_budget(0)` —
asserted in tests and the CI serve smoke; `compile_cache_dir` persists
the XLA compilation cache across restarts (bounded cold start).
""",
    "raft_tpu.parallel.merge": """\
### Cross-shard merge-tier decision table

Every sharded search's candidate merge routes through `merge_topk`
(the obs counter `parallel.merge.dispatch{impl=...}` records the pick;
`merge="auto"|"allgather"|"ring"` on the search entries overrides the
`RAFT_TPU_RING_TOPK` tri-state):

| tier (`impl`) | selected when | transport | merge-phase bytes/rank |
|---|---|---|---|
| `allgather` | auto off-TPU, small/latency-bound shapes, or forced | one `all_gather` of the `[n_dev, m, k]` tables + local select; result replicated | O(n_dev·m·k) — the materialized table (`comms.bytes{op=allgather}`) |
| `ring_kernel` | TPU + whole-mesh 1-D axis + `k ≤ 64` + VMEM guard (`ops.pallas_kernels.ring_topk_kernel_ok`) | Pallas `ring_topk_merge`: n_dev−1 async-remote-DMA hops, each shipping only the surviving `[m/n_dev, k]` block, k-round extraction merge on-chip; result query-sharded | O(m·k) total (per-hop `comms.bytes{op=ring_topk}`, attributed via `Comms.count_ring_topk`) |
| `ring_ppermute` | ring tier forced/auto off-TPU or on a sub-axis of a multi-axis mesh | `Comms.ring_topk_hop` ppermute hops — the kernel's schedule, identical results and identical counted bytes | O(m·k) total (per-hop `comms.bytes{op=ring_topk}`) |
| `ring_fused_scan` | non-refined sharded IVF-PQ where the ring kernel would run (`RAFT_TPU_RING_FUSED` tri-state; l2/ip metrics, int32 ids, supported packed layout, union table ≤ `RING_FUSED_MAX_SEGS`) | ONE persistent kernel (`ops.pallas_kernels.ring_lut_scan_merge`): each hop's exchange hides the NEXT query chunk's LUT scan; the per-shard `[m, k]` candidate table never reaches HBM | identical to the ring tiers (the fusion moves compute, not bytes) |

The ring kernel's hop schedule is `RAFT_TPU_RING_OVERLAP` (auto |
on | off; auto = the half-pipelined overlap schedule, `off` = the
serialized PR-8 exchange kept for bench comparison) — exact parity
either way, see docs/developer_guide.md "The ring schedule".

See docs/developer_guide.md "The cross-shard merge tier" for the full
latency/bandwidth trade and docs/observability.md for the byte model.
""",
    "raft_tpu.neighbors.ivf_pq": """\
### IVF-PQ scan-tier decision table

`search()` picks the scan engine from `SearchParams` + index layout
(the obs counter `ivf_pq.scan.dispatch{impl=...}` records the pick):

| tier (`impl`) | selected when | scan structure | `filter_bitset` handling | HBM transients |
|---|---|---|---|---|
| `per_query` | small batches (`B·n_probes < 2·n_lists`) or grouped memory guards decline | per-query candidate gather + query-only-LUT one-hot contraction (or recon-dot) | in-scan mask (`sample_filter.passes` over candidate ids) | unpacked codes + `[B, n_probes·L]` tables |
| `grouped_xla` | batch scans, `scan_select="exact"/"approx"` | segmented list-centric scan, per-chunk one-hot decode (or recon cache) | in-scan mask before selection | decoded chunks + `[n_seg, seg, k]` accumulators |
| `grouped_pallas` | `scan_select="exact"` + recon cache + VMEM fit (TPU) | fused contraction + running top-k per segment chunk | in-scan mask before selection | `[n_seg, seg, k]` accumulators |
| `segk` | `scan_select="approx"` + recon cache + VMEM fit (TPU); filtered shapes also pass `filtered_scan_mem_ok(slot_bytes=5)` | scalar-prefetch DMA kernel over bf16 recon rows | sentinel-masked id table: filtered slots become the `-1` invalid id BEFORE the kernel's bin pre-selection | `[n_seg, seg, 256]` bin tables (+ the masked `[n_lists, L]` id table when filtered) |
| `pallas_lut` | `scan_select="pallas"`, or `"approx"` auto-upgraded for oversampled shapes (`n_probes ≥ 64` or `k ≥ 400`) with NO recon cache; needs `n_probes·256 ≥ k`; filtered shapes also pass `filtered_scan_mem_ok` (TPU) | fused LUT-scan over PACKED codes: in-kernel n-bit unpack, on-chip ADC Σ_s QLUT[s, code_s], 2-deep bin top-k | packed keep bits (`sample_filter.list_filter_bytes`, 1 bit/candidate) streamed beside the codes, unpacked in-kernel, masked to the ±inf/-1 sentinel BEFORE bin selection | `[n_seg, seg, 256]` bin tables (+ `[n_lists, ceil(L/8)]` filter bytes when filtered) |
| `ring_lut_fused` | sharded (`mesh=`) non-refined search where the ring merge would run (see `parallel.merge`'s table) | the scan folded INTO the ring exchange — one persistent kernel per shard from packed codes to the merged top-k | per-shard byte slice (the replicated global bitset composed with the shard's global-id table) streamed per code tile, same sentinel epilogue | none: chunk candidates live in VMEM only (+ the per-shard filter bytes when filtered) |

Since ISSUE 12, a `filter_bitset` is a streamed per-candidate mask in
every tier, never a dispatch disqualifier: filtered dispatches count
`ivf_pq.scan.dispatch{filtered=1,impl=…}` and the old
`fallback{reason=filter_bitset}` is retired (CI asserts it stays 0).

`lut_dtype` ("auto" | "float32" | "bfloat16" | "float8_e4m3") is the
reference's fp8-LUT accuracy/footprint trade (`ivf_pq_fp_8bit.cuh`):
float32 keeps exact f32 ADC (and exact parity between tiers);
bfloat16 ≈ the TPU decode default, ~1e-2-relative key drift, candidate
overlap ≥ 0.99 in practice; float8_e4m3 quantizes harder — sized for
oversampled scans where the candidate slack absorbs the reordering.
The default "auto" resolves per dispatch (`resolve_lut_dtype`,
counted in `ivf_pq.lut.dispatch{dtype=…}`): **fp8 is the measured
default for oversampled TPU scans** when the candidate slack is ≥
`FP8_LUT_MIN_SLACK`×k, declining to bf16 on thin slack and to exact
f32 for everything else (and everywhere off-TPU unless
`RAFT_TPU_FP8_LUT=on`). The recorded per-dataset recall deltas (bench
`lut_dtype` legs, held by the benchdiff gate) must stay within
`FP8_LUT_RECALL_FLOOR` (0.01 recall@10); a dataset past the floor
pins `lut_dtype="bfloat16"` explicitly. The XLA paths quantize LUT entries, the `pallas_lut` kernel
quantizes its codebook operand — same knob, numerically siblings.

`SearchParams.refine="f32_regen"` + `search(..., dataset=...)` folds
the reference's refinement_rate pattern into the call: the scan runs
at `k·refine_ratio` candidates (through whichever tier above wins) and
the exact re-rank routes through `neighbors.refine`'s dispatch tier —
see that module's decision table.
""",
    "raft_tpu.neighbors.refine": """\
### Refine-tier decision table

`refine()` (and the `refine="f32_regen"` paths of `ivf_pq.search` /
`ivf_flat.search`) picks the re-rank engine from dataset residency +
shape (the obs counter `refine.dispatch{impl=...}` records the pick):

| tier (`impl`) | selected when | gather structure | `filter_bits` handling | HBM transients |
|---|---|---|---|---|
| `pallas_gather` | device-resident f32/bf16 dataset, `k ≤ 64`, `k_cand ≥ 256`; auto on TPU for oversampled shapes (`k_cand ≥ 400` or a `[m, C, d]` buffer past 1 GB), forced with `RAFT_TPU_PALLAS_REFINE=always` (interpret mode off-TPU) | fused kernel (`ops.pallas_kernels.gather_refine_topk`): candidate ids HBM→SMEM, dataset rows streamed HBM→VMEM row-by-row, exact epilogue + running top-k on-chip | each candidate's bitset WORD rides the row-DMA queue (addressed off the same SMEM id); cleared bits poison rows to ±inf/-1 in the metric epilogue | `[m, 128]` result tables only (plus a PER-CALL `[n, ceil(d/128)·128]` pad copy when `d % 128 ≠ 0` — `ivf_common.gather_refine_mem_ok` declines the tier when that copy exceeds the cap or the gather buffer it replaces) |
| `xla_gather` | device dataset, any other shape | `dataset[cand]` gather + one batched einsum + `select_k` | candidate table sentinel-masked BEFORE the gather (`sample_filter.passes` → `-1`) | the `[m, C, d]` f32 gather buffer (7.7 GB at batch 10000 × k_cand 2000 × d 96) |
| `tiered_prefetch` (`refine_landed` via `neighbors.tiered`, ISSUE 17) | host-resident 2-D base on the oversampled search paths, ≥ 2 pipeline sub-batches (or `refine_transfer="tiered"` / `RAFT_TPU_TIERED_REFINE=1` forced), `ivf_common.tiered_refine_mem_ok` | background `RowPrefetcher` gathers ONLY each sub-batch's candidate rows host→HBM under the previous sub-batch's scan (`serve.prefetch.{hit,stall}{tenant=}`); re-rank on already-landed rows | same as host_gather — the scan tiers pre-filter | `(depth+1)` in-flight `[m_b, C, d]` landed blocks |
| `host_gather` (`refine_gathered`) | host/memmapped base (optionally SQ8 via `dequant=`), single sub-batch or `refine_transfer="serial"` | host fancy-index of candidate rows, re-rank on device | none — oversampled callers hand these tiers pre-filtered candidates | `[m, C, d]` host rows + device copy |
| `provider_regen` (`refine_provider`) | device-chunk provider (synthetic regen, deep-100m) | regenerate blocks on device, scatter candidate rows into one buffer | none — same contract as host_gather | `[m·C, d]` device buffer (callers chunk queries) |

All tiers share the metric semantics of the einsum path (l2 / sqrt-l2
/ ip / cosine, invalid ids → ±inf, k ≤ n_candidates validated up
front), so results cannot drift across tiers beyond dtype-tiered
rounding. `filter_bits` (ISSUE 12) is defense in depth on the
oversampled search paths — the scan tiers already exclude filtered
candidates — and the enforcement site for direct callers re-ranking an
unfiltered candidate list; filtered dispatches count
`refine.dispatch{filtered=1,impl=…}`.
""",
}


def first_line(doc):
    if not doc:
        return ""
    for ln in doc.strip().splitlines():
        ln = ln.strip()
        if ln:
            return ln.rstrip(".") + "."
    return ""


def main():
    out = io.StringIO()
    out.write("# API reference\n\n")
    out.write("Public surface, one line per symbol (first docstring "
              "sentence).\nGenerated by ``tools/gen_api_docs.py`` — "
              "re-run it after API changes.\n\nGuides: "
              "[quick start](quick_start.md) · "
              "[observability](observability.md) · "
              "[developer guide](developer_guide.md) · "
              "[TPU design notes](tpu_design_notes.md)\n")
    for mname in MODULES:
        mod = importlib.import_module(mname)
        out.write(f"\n## `{mname}`\n\n")
        head = first_line(mod.__doc__)
        if head:
            out.write(f"{head}\n\n")
        rows = []
        for name, obj in sorted(vars(mod).items()):
            if name.startswith("_"):
                continue
            if getattr(obj, "__module__", None) != mname:
                continue  # re-exports documented at their home module
            if inspect.isclass(obj):
                kind = "class"
            elif callable(obj):
                kind = "def"
            else:
                continue
            rows.append((kind, name, first_line(inspect.getdoc(obj))))
        for kind, name, doc in rows:
            out.write(f"- **{name}** ({kind}) — {doc}\n")
        if not rows:
            out.write("- (module-level constants / data only)\n")
        if mname in NOTES:
            out.write("\n" + NOTES[mname])
    with open("/root/repo/docs/api_reference.md", "w") as f:
        f.write(out.getvalue())
    print(f"wrote docs/api_reference.md "
          f"({len(out.getvalue().splitlines())} lines)")


if __name__ == "__main__":
    main()
