"""Device ms per call of the IVF-Flat search's scan: every instruction
of the search program under ``raft_tpu.ivf_flat.scan`` and outside probe
selection and the gather: the contraction of the queries with the
candidate rows, the per-query or per-segment select, and the merge.
Read as ``ivf_flat_scan_ms.throughput`` in the cells that report
``qps`` (the grouped program, its scan kernel included) and as
``ivf_flat_scan_ms.latency`` in those that report ``p99_ms``; time as
``ivf_flat_stages`` takes it."""

from benchmark import ivf_flat_stages


def read(ctx):
    return ivf_flat_stages.read_stage(ctx, "scan")
