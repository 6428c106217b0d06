#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: serve its traffic at several
mean rates, one window each, in one process, and print per rate the
tails, the queue wait early and late in the window (a backlog that grows
shows as late >> early), and whether requests were left unserved.

    python3 benchmarks/chip/sweep.py --workload stablelm-1.6b.chat \\
        --rates 3,4,5,6 --seconds 20 --seed 5
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from chip import harness
    from chip.stats import percentile
    from repro import compile_cache
    compile_cache.enable()
    cell = harness.Cell.load(args.workload)
    bench = harness.Bench(cell)
    bench.build(args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic["rate_rps"] = rate
        win = bench.window(args.seed, args.seconds)
        recs = win.records
        third = args.seconds / 3

        def wait(lo, hi):
            xs = [r["t_admit"] - r["t_sched"] for r in recs
                  if lo <= r["t_sched"] - win.t0 < hi]
            return percentile(xs, 50) * 1e3 if xs else None

        print(json.dumps({
            "rate": rate, "requests": len(recs),
            "ttft_p50_ms": percentile([r["t_first"] - r["t_sched"]
                                       for r in recs], 50) * 1e3,
            "ttft_p95_ms": percentile([r["t_first"] - r["t_sched"]
                                       for r in recs], 95) * 1e3,
            "tpot_p95_ms": percentile(
                [(r["t_done"] - r["t_first"]) / (r["n_out"] - 1)
                 for r in recs if r["n_out"] > 1], 95) * 1e3,
            "queue_wait_p50_first_third_ms": wait(0, third),
            "queue_wait_p50_last_third_ms": wait(2 * third, 3 * third),
            "drain_s": max(r["t_done"] for r in recs) - win.t1,
            "compiles": win.compiles}), flush=True)


if __name__ == "__main__":
    main()
