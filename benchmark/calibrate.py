#!/usr/bin/env python3
"""Readings for the limits of the comparison that decides ``correct``.

    python3 benchmark/calibrate.py --workload <cell> --seeds 11,12,13 [--seconds 2]
    python3 benchmark/calibrate.py --workload <cell> --seeds 11,12,13 --fault depth1_select

For each seed, in one process (set-up is long, so the seeds share the
compiled programs):

- the program: a whole run of the cell through ``run.run_cell``, with a
  short window at the cell's own load and sizes; its numbers compared
  are the lower readings;
- the control: the plain reference put in the program's place, at the
  nearest precision below the configuration's (float32 matmuls at
  three bf16 passes, as a TPU's ``Precision.HIGH``, for ``highest``;
  ``reference.PRECISIONS``): the exact top-k of the
  same number of queries drawn from the seed, checked by the same
  comparison (its recall against the reference at the configuration's
  precision); its numbers are the upper readings.

With ``--fault`` the program runs with that fault planted (``FAULTS``)
and no control: the numbers the fault reads, for the limits of the
numbers the control does not fail.

One JSON line per seed on standard output. Not part of a benchmark
run; ``limits/<cell>.json`` records what was read and the limits set.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, datagen, reference, run  # noqa: E402
from benchmark.manifest import Bench  # noqa: E402


def control_numbers(cfg, seed: int, precision: str) -> dict:
    """The comparison's numbers for the reference at ``precision`` in
    the program's place, on ``check.SAMPLE_ANSWERS`` queries."""
    import jax.numpy as jnp

    base, queries = datagen.make(cfg["data"], seed)
    pick = check.sample(queries.shape[0], seed)
    q = jnp.asarray(queries)[pick]
    k = cfg["search"]["k"]
    dist, ids = reference.exact_topk(base, q, k, precision=precision)
    _, exact = reference.exact_topk(base, q, k)
    ref_rows = reference.row_distances(base, q, ids)
    return {"bad_ids": check.bad_ids(ids, cfg["data"]["n"]),
            "dist_gap": check.dist_gap(dist, ref_rows),
            "recall_miss": 1.0 - check.recall(ids, exact)}


def depth1_select(set_attr=setattr, tile_rows: int = 16384) -> None:
    """Plant a select fault in the exact search: the per-tile cut keeps
    one entry of each of its 128 strided bins instead of two, and every
    call scans tiles of ``tile_rows`` rows, so that small batches take
    the cut too. A true neighbour that shares a bin with a nearer one is
    lost; every returned distance stays exact. ``set_attr`` sets the
    program's attributes (a test passes ``monkeypatch.setattr``)."""
    import jax
    import jax.numpy as jnp
    from raft_tpu.neighbors import brute_force as bf

    two_best = bf._two_best_per_bin

    def one_best(dists, select_min):
        vals, pos = two_best(dists, select_min)
        half = vals.shape[1] // 2
        return vals.at[:, half:].set(jnp.inf if select_min else -jnp.inf), pos

    set_attr(bf, "_two_best_per_bin", one_best)
    set_attr(bf, "_choose_tiles", lambda m, n, d: (m, min(n, tile_rows)))
    jax.clear_caches()


#: faults that ``--fault`` plants, by name
FAULTS = {"depth1_select": depth1_select}


def main(argv=None, *, require=run.require_tpu, bench=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    bench = bench or Bench(ROOT)
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    devices = require(cell["chips"])
    if args.fault:
        FAULTS[args.fault]()
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        out = run.run_cell(bench, args.workload, seed, args.seconds, False,
                           devices, t)
        row = {"seed": seed, "fault": args.fault,
               "program": {k: v["value"] for k, v in out["checks"].items()},
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        if not args.fault:
            row["control"] = control_numbers(cfg, seed, "bf16_3x")
        row["wall_s"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


if __name__ == "__main__":
    run._use_compile_cache()
    main()
