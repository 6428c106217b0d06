#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 benchmarks/chip/run.py --workload stablelm-1.6b.chat \\
        --seed 7 --seconds 51 --trace 0

From the root of a checkout, on a machine that holds the cell's chips.
Set-up makes the weights from ``--seed`` on the device, builds the
program and compiles (or loads from JAX's persistent cache) every shape
the cell's traffic uses; then the window serves the cell's traffic for
``--seconds``, drains, and the served tokens of a sample of requests are
compared with the plain float32 reference.  ``--trace 1`` records the
window's last seconds with the profiler and reports the cell's
per-layer metrics instead of its end-to-end ones.

Standard error ends with the numbers that decide ``correct``, each with
its limit; the last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``.  Without a TPU, or
with fewer chips than the cell asks for, it exits with code 2 and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))


def say(*parts) -> None:
    print("[chipbench]", *parts, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def device_info(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX has "
                     f"{len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    return devs[:chips]


def main(argv=None, *, cell=None, require_tpu: bool = True,
         control: bool = False) -> dict:
    """One run; ``cell`` and ``require_tpu=False`` let a test drive it at a
    small size on the CPU, and ``control`` judges the control's tokens in
    place of the served ones (``harness.compare``)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chip import harness, stalls
    cell = cell or harness.Cell.load(args.workload)
    from repro import compile_cache
    compile_cache.enable()
    import jax

    if require_tpu:
        devs = device_info(cell.chips)
    else:
        devs = jax.devices()[:cell.chips]
    kind = devs[0].device_kind
    peak = harness.peaks_for(kind) if require_tpu else {
        "flops_bf16": 1.0, "hbm_bytes_per_s": 1.0}

    bench = harness.Bench(cell)
    bench.build(args.seed)
    trace = None
    if args.trace:
        win, trace = harness.trace_window(bench, args.seed, args.seconds)
    else:
        win = bench.window(args.seed, args.seconds)
    setup_s = win.t0 - T_START
    stats = [d.memory_stats() or {} for d in devs]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    bench.release()
    gc.collect()

    run = harness.RunData(cell, win, setup_s, peak, trace)
    metrics = {}
    for m in cell.per_layer if args.trace else cell.end_to_end:
        v = harness.metric_value(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    ok = harness.served_ok(cell, win)
    res = win.result
    t_ref = time.monotonic()
    correct, compared, rows, gaps = harness.compare(cell, win, args.seed,
                                                    control=control)
    t_ref = time.monotonic() - t_ref

    recs = win.records
    late = [r["t_send"] - r["t_sched"] for r in recs]
    from chip.stats import percentile
    say(f"requests: attempted {ok['attempted']} served {res['served']} "
        f"failed {ok['failed']} (scheduled {len(win.plan.reqs)})")
    say(f"samples: ttft {len(recs)}, tpot "
        f"{sum(1 for r in recs if r['n_out'] > 1)}, queue_wait {len(recs)}")
    if late:
        say(f"generator lateness p95 {percentile(late, 95) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms; throttled "
            f"{sum(r['throttled_s'] > 0 for r in recs)} requests")
    say(f"slots_leaked {res['slots_leaked']}, tick_execs - steps "
        f"{res['tick_execs'] - res['steps']}, steps {res['steps']}, "
        f"prefills {res['prefills']}, queue_left {res['queue_left']}, "
        f"backpressure signals {res['bp_signals']}")
    say(f"compiles in window {win.compiles}")
    host = win.host
    say(f"host stalls over {stalls.THRESHOLD * 1e3:.0f} ms in window: "
        f"{len(host['stalls'])}; garbage collections (generation: count, "
        f"total s, longest s) " + ", ".join(
            f"{g}: {n}, {tot:.4f}, {top:.4f}"
            for g, (n, tot, top) in sorted(host["gc"].items())))
    for st in host["stalls"]:
        say("host stall at {at_s:.3f} s: {wall_s:.4f} s, user {user_s:.4f} "
            "s, sys {sys_s:.4f} s, gc {gc_s:.4f} s, involuntary switches "
            "{nivcsw}".format(**st))
    say(f"window {args.seconds} s, set-up {setup_s:.3f} s, reference "
        f"{t_ref:.3f} s over {len(rows)} requests / {gaps.size} tokens, "
        f"peak memory {mem_peak} bytes")
    for k, v in compared.items():
        say(f"compared: {k} {v['value']} limit {v['limit']}"
            + (" (control)" if control else ""))

    out = {"correct": correct, "attempted": ok["attempted"],
           "failed": ok["failed"], "metrics": metrics,
           "device": {"platform": devs[0].platform, "kind": kind,
                      "count": len(devs), "memory_peak_bytes": mem_peak}}
    if trace is not None:
        out["device"]["busy_s"] = trace["busy_ns"] * 1e-9
        out["device"]["window_s"] = trace["window_ns"] * 1e-9
        top = sorted(trace["modules"].items(), key=lambda kv: -kv[1][0])
        out["breakdown"] = {
            "device_ops": [[n, t * 1e-9] for n, (t, _) in top[:10]],
            "idle_gaps": [[n, t * 1e-9] for n, t in trace["idle_gaps"]]}
    out["compared"] = compared
    return out


if __name__ == "__main__":
    try:
        result = main()
    except NoChip as e:
        say(f"no chip: {e}")
        sys.exit(2)
    print(json.dumps(result), flush=True)
