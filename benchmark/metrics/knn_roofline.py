"""Roofline share of the exact search's program, in %. Read as
``knn_roofline.throughput`` in the cells that report ``qps`` and as
``knn_roofline.latency`` in those that report ``p99_ms``.

Work (``work.knn_work``): ``2 * dim`` operations per (query, row) pair
and every row read once; least time = max(ops / 197 TFLOP/s,
bytes / 819 GB/s) on a v5e (``peaks.json``): the operations set it at
batch 10,000, the bytes at batch 10. Time: the device time of the
search's program (``jit_knn``, the whole jitted ``brute_force.knn``)
over the traced window."""

from benchmark import work


def read(ctx):
    return work.knn_roofline(ctx)
