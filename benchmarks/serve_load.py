"""Open-loop serving load: event-driven continuous batching vs the naive
sequential baseline, swept over offered request rates and transports.

Each row replays the same Poisson arrival schedule
(:class:`repro.serve.LoadSpec`) against one server configuration and
reports requests/s, tokens/s, and p50/p99 TTFT / per-token latency.
Latency is measured from the *scheduled* arrival time (coordinated-
omission-honest: a server that falls behind pays for the queueing it
causes).  The ``seq-baseline`` row serves the identical schedule one
request at a time — same jitted steps, same greedy argmax, so the
delta is pure continuous-batching + prefill/decode overlap.

Each JAX phase on this host (the baseline and every in-proc row) runs in
a process of its own, and this process never starts JAX: a chip belongs
to one process at a time, and the socket rows' server process needs it.

``--insights`` runs :func:`repro.insights.analyze` over each event-driven
row's ``Session.stats()`` and prints the findings — under an offered
rate the slots cannot sustain, the ``admission-backpressure`` rule fires
for the ``request`` channel.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.net.launch import run_in_process
from repro.serve import LoadSpec, all_requests, run_sequential, run_serve
from repro.serve.loadgen import summarize


def _print_row(row):
    print(f"  serve {row['impl']:14s} rps={row['rps']:5.1f} "
          f"req/s={row['requests_per_s']:6.2f} "
          f"tok/s={row['tokens_per_s']:7.1f} "
          f"ttft p50={row['ttft_p50_ms']:7.1f}ms "
          f"p99={row['ttft_p99_ms']:7.1f}ms "
          f"tok p50={row['per_token_p50_ms']:5.2f}ms")


def _sequential_summary(cfg, reqs, max_len: int, seed: int):
    recs = run_sequential(cfg, reqs, max_len=max_len, seed=seed)
    span = max(x["t_done"] for x in recs) - min(x["t_sched"] for x in recs)
    return summarize(recs, span)


def run(rps=(4.0, 16.0), requests: int = 24, clients: int = 2,
        slots: int = 4, max_len: int = 64,
        transports=("inproc", "socket"), procs: int = 2,
        arch: str = "gemma3-1b", queue_bound: int = 8,
        insights: bool = False, out: str = None, seed: int = 0):
    """One result row per (impl, rps); all rows share the arrival
    schedule at a given rps, so columns are directly comparable."""
    from repro.configs import ARCHS, reduce_cfg

    cfg = reduce_cfg(ARCHS[arch].cfg)
    rows = []
    all_findings = []
    for r in rps:
        load = LoadSpec(rps=float(r), requests=requests, seed=seed)
        reqs = all_requests(load, clients, cfg.vocab)
        row = {"impl": "seq-baseline", "rps": float(r),
               "transport": "-", "slots": 1,
               **run_in_process(_sequential_summary, cfg, reqs, max_len,
                                seed)}
        rows.append(row)
        _print_row(row)
        for tr in transports:
            kw = dict(arch=arch, clients=clients, slots=slots,
                      max_len=max_len, load=load, queue_bound=queue_bound,
                      transport=tr, seed=seed)
            # a socket round spawns its own rank processes
            res = (run_serve(procs=procs, **kw) if tr == "socket"
                   else run_in_process(run_serve, **kw))
            row = {"impl": f"edat-{tr}", "rps": float(r),
                   "transport": tr, "slots": slots,
                   **res["summary"],
                   "steps": res["result"]["steps"],
                   "tick_execs": res["result"]["tick_execs"],
                   "bp_signals": res["result"]["bp_signals"]}
            rows.append(row)
            _print_row(row)
            if insights:
                from repro.insights import analyze
                found = analyze(res["stats"])
                all_findings.extend(
                    {"impl": row["impl"], "rps": float(r),
                     "rule": f.rule, "message": f.message}
                    for f in found)
                for f in found:
                    print(f"    insight [{f.rule}] {f.message}")

    result = {"requests": requests, "clients": clients, "slots": slots,
              "max_len": max_len, "arch": arch, "rows": rows,
              "findings": all_findings}
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
    return result


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out", nargs="?", default=None,
                    help="optional path for the bench JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: one rate, few requests, inproc only "
                         "unless --transport socket")
    ap.add_argument("--transport", choices=("inproc", "socket", "both"),
                    default="both")
    ap.add_argument("--rps", type=float, nargs="+", default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--queue-bound", type=int, default=8)
    ap.add_argument("--insights", action="store_true",
                    help="run repro.insights over each event-driven row")
    a = ap.parse_args()
    transports = (("inproc", "socket") if a.transport == "both"
                  else (a.transport,))
    if a.smoke:
        rps = tuple(a.rps) if a.rps else (8.0,)
        requests = a.requests or 6
        if a.transport == "both":
            transports = ("inproc",)
    else:
        rps = tuple(a.rps) if a.rps else (4.0, 16.0)
        requests = a.requests or 24
    run(rps=rps, requests=requests, clients=a.clients, slots=a.slots,
        max_len=a.max_len, transports=transports, procs=a.procs,
        queue_bound=a.queue_bound, insights=a.insights, out=a.out)
