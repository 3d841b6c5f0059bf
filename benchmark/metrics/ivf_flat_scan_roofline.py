"""Roofline share of the IVF-Flat scan kernel, in %.

Work (``work.py``): ``2 * dim`` operations per scanned (query, row)
pair and every probed list's float32 rows and ids read once, from the
real probe assignment; least time = max(ops / 197 TFLOP/s,
bytes / 819 GB/s) on a v5e (``peaks.json``), which the operations set
at these sizes (compute bound). Time: the device time of the scan
kernel's events in the trace, matched by the names below: the
segmented scan (``segk``) or the grouped scan."""

from benchmark import work

KERNELS = ("segmented_scan_topk", "grouped_scan_topk")


def read(ctx):
    dim = ctx.config["data"]["dim"]
    return work.scan_roofline(ctx, KERNELS, row_bytes=4 * dim,
                              ops_per_pair=2 * dim)
