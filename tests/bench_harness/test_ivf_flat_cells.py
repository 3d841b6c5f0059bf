"""The IVF-Flat cells of the benchmark (``sift1m-ivfflat-nlist1024``)
run end to end on the CPU, through ``benchmark/run.py``, on the
harness's tiny tree (``test_harness.make_tree``): the uncapped index
holds every row, the per-query path answers the batch-10 cell and the
grouped path the batch-10,000 one, each passes the committed
comparison, and the per-stage readers find nothing to read in a CPU
trace (no TPU plane) and say so without raising.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.manifest import Bench  # noqa: E402
from tests.bench_harness.test_harness import (  # noqa: E402
    TINY_DATA, _run, make_tree, tpu_dispatch)

CONFIG = "sift1m-ivfflat-nlist1024"
CELLS = {CONFIG + ".batch10": "per_query", CONFIG + ".batch10k": "grouped"}

__all__ = ["tpu_dispatch"]  # the fixture, used by name below


#: lists of the tiny tree's configuration: enough that a batch of 10 at
#: the tree's 4 probes stays under the grouped path's switch (batch x
#: probes >= 2 x lists) and its batch of 128 goes over it, as on the chip
TINY_LISTS = 32


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = make_tree(str(tmp_path_factory.mktemp("bench_ivf_flat")))
    path = os.path.join(root, "benchmark", "configs", CONFIG + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["index"]["n_lists"] = TINY_LISTS
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    return root


def test_config_is_uncapped_and_exact():
    cfg = Bench(ROOT).config(CONFIG)
    assert cfg["index"] == {"n_lists": 1024, "list_size_cap_factor": None,
                            "spill": False}
    assert cfg["search"] == {"k": 10, "n_probes": 50}
    assert cfg["reduced"] == []


@pytest.mark.parametrize("traced", [0, 1])
@pytest.mark.parametrize("cell", list(CELLS))
def test_ivf_flat_cell_runs_end_to_end(tree, tpu_dispatch, monkeypatch,
                                       capsys, cell, traced):
    from raft_tpu.neighbors import ivf_flat

    routes, built = [], []
    plan, build = ivf_flat._plan, ivf_flat.build

    def spy_plan(*a, **kw):
        program, args, kwargs = plan(*a, **kw)
        routes.append(program)
        return program, args, kwargs

    def spy_build(*a, **kw):
        built.append(build(*a, **kw))
        return built[-1]

    monkeypatch.setattr(ivf_flat, "_plan", spy_plan)
    monkeypatch.setattr(ivf_flat, "build", spy_build)
    out = _run(tree, cell, traced)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(out))
    assert out["correct"] is True and out["failed"] == 0
    index = built[0]
    assert index.size == TINY_DATA["n"]        # every row kept
    want = {"per_query": ivf_flat._search_impl,
            "grouped": ivf_flat._search_grouped}[CELLS[cell]]
    assert set(routes) == {want}
    bench = Bench(tree)
    if traced:
        # no TPU plane in a CPU trace: the device readers find nothing
        assert set(out["metrics"]) == {
            m["name"] for m in bench.metrics(cell, trace=True)
            if m["source"] != "device_trace"}
    else:
        assert set(out["metrics"]) == {
            m["name"] for m in bench.metrics(cell, trace=False)}
        assert out["metrics"]["recall_at_10"]["value"] > 0.5
