#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the
system sustains, completions equal to offers with no backlog growing
through the window.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --rates 500,1000,2000 [--seconds 5]

Builds the cell's index once and runs its open-loop driver at each
offered rate in turn, printing one JSON line per rate: the completed
rate, the median and 99th-percentile latency from the due time, how
late the generator ran, and the mean latency of the last third of the
requests over that of the first third (a backlog that grows through
the window reads well above 1). The cell's traffic file takes the
chosen rate as a number; a benchmark run never sweeps.
"""

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import datagen, drivers, run, stats  # noqa: E402
from benchmark.manifest import Bench  # noqa: E402


def main(argv=None, *, require=run.require_tpu, bench=None):
    import jax

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = bench or Bench(ROOT)
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    require(cell["chips"])
    system = bench.system(cfg["system"])
    base, queries = datagen.make(cfg["data"], args.seed)
    index = jax.block_until_ready(system.build(cfg, base))
    session = drivers.prepare(traffic, cfg, system, index, base, queries,
                              args.seed)
    rows = []
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            session.rate = rate
            w = session.run(args.seconds)
            third = max(1, len(w.latency_s) // 3)
            row = {"offered_per_s": rate,
                   "completed_per_s": w.answered / (w.t_last - w.t0),
                   "failed": w.failed,
                   "p50_ms": 1e3 * stats.percentile(w.latency_s, 50),
                   "p99_ms": 1e3 * stats.percentile(w.latency_s, 99),
                   "late_p99_ms": 1e3 * stats.percentile(w.late_s, 99),
                   "backlog_growth": statistics.mean(w.latency_s[-third:])
                   / statistics.mean(w.latency_s[:third])}
            print(json.dumps(row), flush=True)
            rows.append(row)
    finally:
        session.close()
    return rows


if __name__ == "__main__":
    run._use_compile_cache()
    main()
