"""Device ms per call of the exact search outside its distance and
select stages: the per-call layout work (``raft_tpu.brute_force.prepare``:
the base padded and cut into tiles, the answer finalized) and every
instruction the stage map leaves unmapped (a loop's counter). Read as
``knn_other_ms.throughput`` in the cells that report ``qps`` and as
``knn_other_ms.latency`` in those that report ``p99_ms``; time as in
``knn_distance_ms``."""

from benchmark import scopes


def read(ctx):
    ms = scopes.knn_group_ms(ctx)
    return None if ms is None else ms["other"]
