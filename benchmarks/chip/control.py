#!/usr/bin/env python3
"""The readings that set a cell's limit on ``max_logit_gap``, on the chip,
at the cell's own size and load, for many seeds in one process.

For each seed: the weights of that seed on the warm engine, one window of
the cell's traffic, then on the same sampled requests (prompt and served
tokens) the program's reading (the widest gap between a served token's
float32 reference logit and the reference's best) and the control's (the
same gap for the token that the reference run in fp8 puts first), each
judged against the cell's limit by ``harness.compare``, the comparison
that decides ``correct`` in every run.  One JSON line per seed;
``harness.py`` holds how the sample is drawn.

    python3 benchmarks/chip/control.py --workload stablelm-1.6b.chat \\
        --seeds 1,2,3 --seconds 15
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    from chip import harness
    from repro import compile_cache
    compile_cache.enable()
    cell = harness.Cell.load(args.workload)
    bench = harness.Bench(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        if i == 0:
            bench.build(seed)
        else:
            bench.set_weights(seed)
        win = bench.window(seed, args.seconds)
        # the reference makes its own copy of the weights: free the
        # program's first
        bench.engine.params = None
        gc.collect()
        t = time.monotonic()
        ok, prog, rows, served = harness.compare(cell, win, seed)
        ref_s = time.monotonic() - t
        c_ok, ctrl, _, c_gaps = harness.compare(cell, win, seed,
                                                control=True)
        print(json.dumps({
            "seed": seed, "requests": len(win.records),
            **harness.served_ok(cell, win),
            "slots_leaked": win.result["slots_leaked"],
            "rows": len(rows), "tokens": int(served.size),
            "program": prog["max_logit_gap"]["value"], "correct": ok,
            "control": ctrl["max_logit_gap"]["value"],
            "control_correct": c_ok,
            "limit": prog["max_logit_gap"]["limit"],
            "program_p99": float(np.quantile(served, 0.99)),
            "control_median": float(np.median(c_gaps)),
            "reference_s": ref_s, "compiles": win.compiles}), flush=True)


if __name__ == "__main__":
    main()
