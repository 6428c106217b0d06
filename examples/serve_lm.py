"""Event-driven LM serving: continuous batching driven by EDAT events.

Thin CLI over :mod:`repro.serve` — the promoted, tested subsystem this
example used to sketch.  Client ranks replay an open-loop Poisson
schedule of request events; the server rank admits them into decode
slots, a single self-sustaining ``decode_tick`` chain steps the whole
batch one greedy token at a time, and completions fire back as response
events — the paper's fire-and-forget interaction end to end, with
event-carried backpressure when the admission queue outgrows its bound.

  PYTHONPATH=src python examples/serve_lm.py --arch gemma3-1b --requests 12
  PYTHONPATH=src python examples/serve_lm.py --transport socket --procs 2
"""
import argparse

from repro import compile_cache
from repro.configs import ARCHS
from repro.serve import DEFAULT_MAX_LEN, LoadSpec, run_serve


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=sorted(ARCHS))
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8,
                    help="total requests across all clients")
    ap.add_argument("--rps", type=float, default=8.0,
                    help="aggregate offered request rate")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=DEFAULT_MAX_LEN)
    ap.add_argument("--queue-bound", type=int, default=8)
    ap.add_argument("--transport", choices=("inproc", "socket"),
                    default="inproc")
    ap.add_argument("--procs", type=int, default=None,
                    help="processes for socket runs")
    args = ap.parse_args()

    load = LoadSpec(rps=args.rps, requests=args.requests,
                    max_new_lo=max(1, args.max_new // 2),
                    max_new_hi=args.max_new)
    out = run_serve(arch=args.arch, clients=args.clients, slots=args.slots,
                    max_len=args.max_len, load=load,
                    queue_bound=args.queue_bound,
                    transport=args.transport, procs=args.procs)
    res, s = out["result"], out["summary"]
    print(f"served {res['served']} requests / {s['tokens']} tokens in "
          f"{s['wall_s']:.2f}s serving window "
          f"({s['tokens_per_s']:.1f} tok/s, batch slots={res['slots']}, "
          f"{args.transport})")
    print(f"ttft p50={s['ttft_p50_ms']:.0f}ms p99={s['ttft_p99_ms']:.0f}ms "
          f"per-token p50={s['per_token_p50_ms']:.2f}ms "
          f"p99={s['per_token_p99_ms']:.2f}ms")
    if res["bp_signals"]:
        print(f"backpressure: {res['bp_signals']} on-signal(s); clients "
              f"throttled "
              f"{sum(r['throttled_s'] for r in res['records']):.2f}s total")
    assert res["served"] == args.requests, res
    assert res["slots_leaked"] == 0, res
    assert res["tick_execs"] == res["steps"], res


if __name__ == "__main__":
    main()
