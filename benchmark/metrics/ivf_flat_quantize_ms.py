"""Device ms per call of the IVF-Flat search's probe selection: the
query-to-center contraction and the select of each query's
``n_probes`` nearest lists (scope ``raft_tpu.ivf_flat.coarse_quantize``),
with the unscoped instructions that feed it or read it first. Read as
``ivf_flat_quantize_ms.throughput`` in the cells that report ``qps``
(the grouped program) and as ``ivf_flat_quantize_ms.latency`` in those
that report ``p99_ms`` (the per-query program); time as
``ivf_flat_stages`` takes it."""

from benchmark import ivf_flat_stages


def read(ctx):
    return ivf_flat_stages.read_stage(ctx, "quantize")
