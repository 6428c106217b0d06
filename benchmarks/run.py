"""Benchmark orchestrator: one benchmark per paper table/figure.

  python -m benchmarks.run            # small defaults (CI-sized)
  python -m benchmarks.run --full     # paper-shaped sweeps (slow on 1 core)

This process never starts JAX: each benchmark runs its JAX phases in
processes of their own, since a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--outdir", default="experiments/bench")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    os.makedirs(args.outdir, exist_ok=True)

    print("== runtime micro-overheads (paper §V overhead discussion) ==")
    from benchmarks import runtime_micro
    runtime_micro.run(out=os.path.join(args.outdir, "runtime_micro.json"),
                      transport="both", durable=True)

    print("== Graph500 BFS: EDAT vs BSP reference (paper Fig 3) ==")
    from benchmarks import bfs_scaling
    if args.full:
        bfs_scaling.run(scale=16, ranks=(1, 2, 4, 8, 16), roots=8,
                        out=os.path.join(args.outdir, "bfs.json"))
    else:
        bfs_scaling.run(scale=12, ranks=(1, 2, 4), roots=2,
                        out=os.path.join(args.outdir, "bfs.json"))
    print("== Graph500 BFS across OS processes (SocketTransport) ==")
    bfs_scaling.run(scale=11 if not args.full else 14, ranks=(2, 4),
                    roots=2, transport="socket",
                    out=os.path.join(args.outdir, "bfs_socket.json"))

    print("== In-situ analytics: EDAT vs bespoke (paper Fig 5) ==")
    from benchmarks import insitu
    if args.full:
        insitu.run(analytics=(1, 2, 4, 8, 16), items=128,
                   out=os.path.join(args.outdir, "insitu.json"))
    else:
        insitu.run(analytics=(1, 2, 4), items=32,
                   out=os.path.join(args.outdir, "insitu.json"))
    print("== In-situ analytics across OS processes (SocketTransport) ==")
    insitu.run(analytics=(1, 2), items=32, transport="socket",
               out=os.path.join(args.outdir, "insitu_socket.json"))

    print("== elastic trainer: in-proc vs distributed (steps/s) ==")
    from benchmarks import trainer_scaling
    trainer_scaling.run(steps=8 if not args.full else 20, ranks=(1, 2),
                        out=os.path.join(args.outdir, "trainer.json"))
    trainer_scaling.run(steps=8 if not args.full else 20, ranks=(2, 4),
                        transport="socket",
                        out=os.path.join(args.outdir,
                                         "trainer_socket.json"))

    print("== LM serving under open-loop load (event-driven vs "
          "sequential) ==")
    from benchmarks import serve_load
    if args.full:
        serve_load.run(rps=(4.0, 8.0, 16.0), requests=32,
                       transports=("inproc", "socket"), insights=True,
                       out=os.path.join(args.outdir, "serve_load.json"))
    else:
        serve_load.run(rps=(8.0,), requests=12, transports=("inproc",),
                       insights=True,
                       out=os.path.join(args.outdir, "serve_load.json"))

    print("benchmarks complete; json in", args.outdir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
