"""Device ms per call of the exact search's distance stage: the
contraction and the norms it uses (scope
``raft_tpu.brute_force.distance``), and whatever unscoped instruction
feeds it or reads it first. Read as ``knn_distance_ms.throughput`` in the
cells that report ``qps`` and as ``knn_distance_ms.latency`` in those
that report ``p99_ms``.

Time: the ops of ``jit_knn`` in the traced window, put down to stages by
the map ``scopes.knn_stage_map`` takes from the executable that ran,
over the window's calls. The three stage metrics sum to the program's
device time per call, less the gaps between its ops."""

from benchmark import scopes


def read(ctx):
    ms = scopes.knn_group_ms(ctx)
    return None if ms is None else ms["distance"]
