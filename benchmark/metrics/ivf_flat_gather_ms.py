"""Device ms per call of the per-query IVF-Flat search's gather of each
query's probed list blocks (vectors, ids and norms: scope
``raft_tpu.ivf_flat.gather``), with the unscoped instructions that feed
it or read it first; an XLA gather that the compiler fuses into its
consumer's fusion counts with that consumer. Read as
``ivf_flat_gather_ms.latency`` in the cells that report ``p99_ms``;
time as ``ivf_flat_stages`` takes it."""

from benchmark import ivf_flat_stages


def read(ctx):
    return ivf_flat_stages.read_stage(ctx, "gather")
