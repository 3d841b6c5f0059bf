"""Data of a configuration, made on the device from the run's seed.

``hard_clusters`` follows the design of ``make_synthetic_hard`` in the
program's bench package (many tiny Gaussian clusters, so a query's
top-10 crosses cluster and list boundaries and IVF recall rises with
the probe count as on SIFT-1M), generated here in one jitted call from
the seed instead of on the host:

- ``n // rows_per_cluster`` centers drawn from N(0, 1)^dim;
- each center's nearest-other-center distance, estimated against 256
  sampled centers (self pairs masked by index, matmul at HIGHEST);
- a row is its center plus N(0, s^2) per dimension, with
  ``s = sigma * nearest / sqrt(dim)``; queries come from the same
  distribution on their own key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_SAMPLED_CENTERS = 256


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=(
    "n", "dim", "n_queries", "rows_per_cluster", "sigma"))
def _hard_clusters(key, *, n, dim, n_queries, rows_per_cluster, sigma):
    n_centers = max(64, n // rows_per_cluster)
    kc, ks, ka, kn, kqa, kqn = jax.random.split(key, 6)
    centers = jax.random.normal(kc, (n_centers, dim), jnp.float32)
    n_sub = min(n_centers, _SAMPLED_CENTERS)
    sel = jax.random.choice(ks, n_centers, (n_sub,), replace=False)
    sub = centers[sel]
    cross = jnp.dot(centers, sub.T, precision=jax.lax.Precision.HIGHEST)
    d2 = (jnp.sum(centers * centers, 1)[:, None]
          + jnp.sum(sub * sub, 1)[None, :] - 2.0 * cross)
    d2 = jnp.maximum(d2, 0.0)
    d2 = jnp.where(jnp.arange(n_centers)[:, None] == sel[None, :],
                   jnp.inf, d2)
    scale = sigma * jnp.sqrt(jnp.min(d2, axis=1)) / jnp.sqrt(float(dim))

    def sample(ka_, kn_, m):
        assign = jax.random.randint(ka_, (m,), 0, n_centers)
        noise = jax.random.normal(kn_, (m, dim), jnp.float32)
        return centers[assign] + scale[assign][:, None] * noise

    return sample(ka, kn, n), sample(kqa, kqn, n_queries)


def make(data: dict, seed: int):
    """``(base [n, dim], queries [n_queries, dim])`` float32 on the
    default device, for the configuration's ``data`` block."""
    if data["generator"] != "hard_clusters":
        raise ValueError(f"unknown generator {data['generator']!r}")
    base, queries = _hard_clusters(
        seed_key(seed), n=data["n"], dim=data["dim"],
        n_queries=data["n_queries"],
        rows_per_cluster=data["rows_per_cluster"], sigma=data["sigma"])
    return jax.block_until_ready((base, queries))
