"""Device ms per call of the exact search's select: the per-tile
candidate cut (``raft_tpu.brute_force.tile_cut``), the running merge
(``raft_tpu.brute_force.merge``) and the single-block select
(``raft_tpu.select_k``), with the unscoped instructions that feed them
(a layout copy of the distance block). Read as
``knn_select_ms.throughput`` in the cells that report ``qps`` and as
``knn_select_ms.latency`` in those that report ``p99_ms``; time as in
``knn_distance_ms``."""

from benchmark import scopes


def read(ctx):
    ms = scopes.knn_group_ms(ctx)
    return None if ms is None else ms["select"]
