"""The plain reference: exact squared-L2 k-nearest neighbours.

Straightforward ``jax.numpy`` in float32, independent of the program:
it imports nothing of it and takes nothing it made. The top-k is exact
(``lax.top_k`` over every base row), computed in blocks of queries and
base rows so that it fits beside the data. The distance of a given row
is taken in difference form, ``sum((q - x)^2)``, on the vector unit,
so it does not depend on any matmul precision.

Every configuration of the benchmark answers the same question, the
k nearest rows in squared L2, so they share this reference; a
configuration names it under ``reference``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: matmul precisions of the top-k's contraction. ``highest`` is the
#: configuration's (float32-accurate); ``bf16_3x``, the control, is the
#: three-pass product of ``Precision.HIGH`` (hi*hi + hi*lo + lo*hi),
#: emulated with explicit bfloat16 splits so that it reads the same on
#: every backend.
PRECISIONS = ("highest", "bf16_3x")


def _bf16(x):
    """``x`` rounded to bfloat16's precision, kept in float32. An
    ``astype`` round trip would not do: a TPU compile may keep the
    float32 value inside a fusion (excess precision), which turns the
    three-pass split into one pass."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _dot(q, x, precision: str):
    """``q @ x.T`` in float32 at ``precision``."""
    def dot(a, b):
        return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)

    if precision == "highest":
        return dot(q, x)
    q_hi, x_hi = _bf16(q), _bf16(x)
    if precision == "bf16_3x":
        return (dot(q_hi, x_hi) + dot(q_hi, _bf16(x - x_hi))
                + dot(_bf16(q - q_hi), x_hi))
    raise ValueError(f"unknown precision {precision!r}: {PRECISIONS}")


@functools.partial(jax.jit, static_argnames=("k", "n", "q_tile", "block",
                                             "precision"))
def _topk(base_p, queries_p, *, k, n, q_tile, block, precision):
    n_blocks = base_p.shape[0] // block
    blocks = base_p.reshape(n_blocks, block, -1)

    def one_tile(q):
        q_sq = jnp.sum(q * q, axis=1)

        def one_block(carry, args):
            best_d, best_i = carry
            j, x = args
            s = _dot(q, x, precision)
            d = q_sq[:, None] + jnp.sum(x * x, axis=1)[None, :] - 2.0 * s
            col = j * block + jnp.arange(block, dtype=jnp.int32)
            d = jnp.where(col[None, :] < n, d, jnp.inf)
            v, p = lax.top_k(-d, k)
            cand_d = jnp.concatenate([best_d, -v], axis=1)
            cand_i = jnp.concatenate([best_i, col[p]], axis=1)
            v2, p2 = lax.top_k(-cand_d, k)
            return (-v2, jnp.take_along_axis(cand_i, p2, axis=1)), None

        init = (jnp.full((q.shape[0], k), jnp.inf, jnp.float32),
                jnp.full((q.shape[0], k), -1, jnp.int32))
        (d, i), _ = lax.scan(one_block, init,
                             (jnp.arange(n_blocks, dtype=jnp.int32), blocks))
        return d, i

    tiles = queries_p.reshape(-1, q_tile, queries_p.shape[1])
    d, i = lax.map(one_tile, tiles)
    return d.reshape(-1, k), i.reshape(-1, k)


def exact_topk(base, queries, k: int, precision: str = "highest",
               q_tile: int = 1024, block: int = 1 << 17):
    """Exact top-``k`` of every query over every base row:
    ``(distances [m, k], ids [m, k])`` as numpy, nearest first, the
    distances in expanded form with the matmul at ``precision``."""
    n, m = base.shape[0], queries.shape[0]
    block = min(block, -(-n // 8) * 8)
    q_tile = min(q_tile, -(-m // 8) * 8)
    base_p = jnp.pad(base, ((0, -n % block), (0, 0)))
    queries_p = jnp.pad(queries, ((0, -m % q_tile), (0, 0)))
    d, i = _topk(base_p, queries_p, k=k, n=n, q_tile=q_tile, block=block,
                 precision=precision)
    return np.asarray(d)[:m], np.asarray(i)[:m]


@jax.jit
def _row_distances(base, queries, ids):
    rows = base[jnp.clip(ids, 0, base.shape[0] - 1)]       # [m, k, d]
    diff = queries[:, None, :] - rows
    d = jnp.sum(diff * diff, axis=2)
    return jnp.where((ids >= 0) & (ids < base.shape[0]), d, jnp.nan)


def row_distances(base, queries, ids) -> np.ndarray:
    """Squared L2 distance of ``queries[j]`` to each ``base[ids[j, i]]``
    in difference form; NaN where an id is not a row."""
    return np.asarray(_row_distances(base, jnp.asarray(queries),
                                     jnp.asarray(ids, jnp.int32)))
