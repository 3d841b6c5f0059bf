"""Exact k-nearest-neighbour search through the program's public
entries: ``raft_tpu.neighbors.brute_force.build`` / ``knn``. The serving
front end takes only IVF tenants, so this system has no ``admit``."""

from __future__ import annotations

from raft_tpu.neighbors import brute_force


def build(cfg, base):
    return brute_force.build(base, metric=cfg["data"]["metric"],
                             **cfg["index"])


def searcher(cfg, index, base):
    k = cfg["search"]["k"]
    return lambda queries: brute_force.knn(index, queries, k)
