"""Roofline share of the IVF-PQ scan kernel, in %.

Work (``work.py``): ``pq_dim`` lookup-adds per scanned (query, row)
pair and every probed list's codes and ids read once, from the real
probe assignment; least time = max(ops / 197 TFLOP/s, bytes / 819 GB/s)
on a v5e (``peaks.json``), which the operations set at these sizes
(compute bound). Time: the device time of the scan kernel's events in
the trace, matched by the names below, whichever tier the search
dispatched: the segmented scan over the bf16 reconstruction cache
(``segk``), the fused LUT scan, or the grouped scan."""

import math

from benchmark import work

KERNELS = ("segmented_scan_topk", "ivfpq_lut_scan_topk",
           "grouped_scan_topk")


def read(ctx):
    pq_dim, pq_bits = ctx.config["index"]["pq_dim"], ctx.config["index"]["pq_bits"]
    return work.scan_roofline(ctx, KERNELS,
                              row_bytes=math.ceil(pq_dim * pq_bits / 8),
                              ops_per_pair=pq_dim)
