"""The work of a search call, counted from the algorithm, so it reads the
same whatever kernel or tier does it.

An exact search of ``m`` queries over ``n`` rows of ``dim``: ``2 * dim``
operations per (query, row) pair (a multiply and an add per dimension),
and the rows read once with their norms, the queries read once and the
``k`` (distance, id) results written once.

An IVF scan, from the real probe assignment:

- the probe assignment: each query's ``n_probes`` nearest list centers,
  exact (matmul at HIGHEST, ``lax.top_k``);
- pairs scanned: the sizes of the probed lists, summed over the queries;
- operations: ``2 * dim`` per pair for a flat scan (a multiply and an
  add per dimension), ``pq_dim`` lookup-adds per pair for a PQ scan;
- bytes: every list probed by the call read once (its rows' stored
  vectors or codes, and their 4-byte ids), the queries read once, and
  the ``k`` (distance, id) results of each query written once.

The least time of the call on a chip is the larger of operations over
the peak rate and bytes over the peak bandwidth; a kernel's roofline
share is that least time over the kernel's device time.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("n_probes",))
def _probes(centers, queries, n_probes):
    s = jnp.dot(queries, centers.T, precision=jax.lax.Precision.HIGHEST)
    d = jnp.sum(centers * centers, axis=1)[None, :] - 2.0 * s
    return jax.lax.top_k(-d, n_probes)[1]


def probe_lists(centers, queries, n_probes: int) -> np.ndarray:
    """``[m, n_probes]`` list ids each query probes."""
    return np.asarray(_probes(jnp.asarray(centers, jnp.float32),
                              jnp.asarray(queries, jnp.float32), n_probes))


def call_work(probes: np.ndarray, list_sizes: np.ndarray,
              row_bytes: int, ops_per_pair: int, dim: int,
              k: int) -> Dict[str, float]:
    """Operations and bytes of one call whose queries probe ``probes``."""
    sizes = np.asarray(list_sizes, np.int64)
    pairs = int(sizes[probes].sum())
    rows_read = int(sizes[np.unique(probes)].sum())
    m = probes.shape[0]
    return {"pairs": pairs,
            "ops": float(pairs) * ops_per_pair,
            "bytes": float(rows_read) * (row_bytes + 4)
            + m * dim * 4.0 + m * k * 8.0}


def knn_work(m: int, n: int, dim: int, k: int) -> Dict[str, float]:
    """Operations and bytes of one exact search call."""
    return {"ops": 2.0 * m * n * dim,
            "bytes": n * (dim * 4.0 + 4.0) + m * dim * 4.0 + m * k * 8.0}


def knn_roofline(ctx, programs=("jit_knn",)):
    """The exact search's roofline share over a closed-loop window: the
    work of every call in the window against the device time of the
    search's program runs in its trace. None where the trace holds no
    run of ``programs``."""
    seconds = ctx.trace.module_seconds(programs)
    calls = len(ctx.window.batches)
    if seconds <= 0 or not calls:
        return None
    data = ctx.config["data"]
    w = knn_work(ctx.traffic["batch"], data["n"], data["dim"],
                 ctx.config["search"]["k"])
    peaks = ctx.peaks()
    share, _ = roofline_share(calls * w["ops"], calls * w["bytes"], seconds,
                              peaks["bf16_flops_per_s"],
                              peaks["hbm_bytes_per_s"])
    return share


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak_ops: float, peak_bytes: float):
    """``(share in %, bound)``: the least time over ``seconds``, and
    whether operations or bytes set the least time."""
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bytes
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound


def scan_roofline(ctx, kernels, row_bytes: int, ops_per_pair: int):
    """A scan kernel's roofline share over a closed-loop window: the
    work of every call in the window against the kernel's device time
    in its trace. None where the trace holds none of ``kernels``."""
    seconds = ctx.trace.kernel_seconds(kernels)
    if seconds <= 0 or not ctx.window.batches:
        return None
    cfg, index = ctx.config, ctx.index
    ops = nbytes = 0.0
    for b in set(ctx.window.batches):
        rows = ctx.session.batch_rows[b]
        probes = probe_lists(index.centers, ctx.queries[rows],
                             cfg["search"]["n_probes"])
        w = call_work(probes, index.list_sizes, row_bytes, ops_per_pair,
                      cfg["data"]["dim"], cfg["search"]["k"])
        calls = ctx.window.batches.count(b)
        ops += calls * w["ops"]
        nbytes += calls * w["bytes"]
    peaks = ctx.peaks()
    share, _ = roofline_share(ops, nbytes, seconds,
                              peaks["bf16_flops_per_s"],
                              peaks["hbm_bytes_per_s"])
    return share
