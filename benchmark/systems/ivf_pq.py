"""IVF-PQ with an exact float32 re-rank, through the program's public
entries: ``raft_tpu.neighbors.ivf_pq.build`` / ``search``, and a tenant
of ``raft_tpu.serve``. The configuration's ``index`` and ``search``
blocks are the entries' own parameter names."""

from __future__ import annotations

from raft_tpu.neighbors import ivf_pq

TENANT = "bench"


def _params(cfg):
    search = {key: val for key, val in cfg["search"].items() if key != "k"}
    return (ivf_pq.IndexParams(metric=cfg["data"]["metric"], **cfg["index"]),
            ivf_pq.SearchParams(**search))


def build(cfg, base):
    return ivf_pq.build(base, _params(cfg)[0])


def searcher(cfg, index, base):
    params, k = _params(cfg)[1], cfg["search"]["k"]
    return lambda queries: ivf_pq.search(index, queries, k, params,
                                         dataset=base)


def admit(cfg, registry, index, base):
    registry.admit(TENANT, index, params=_params(cfg)[1], dataset=base,
                   default_k=cfg["search"]["k"])
    return TENANT
