"""Elastic event-driven training — in one process or across many.

The same EventDrivenTrainer program attaches to threads-as-ranks or to
spawned OS processes (several ranks per process over the coalescing
socket transport); with ``--kill`` one process is SIGKILLed mid-run and
the co-located survivors roll back to the last durable checkpoint,
re-shard, and finish (the paper's §VII RANK_FAILED story, for real
processes).  Everything runs through the v2 ``edat.Session``:

    PYTHONPATH=src python examples/train_elastic.py                # threads
    PYTHONPATH=src python examples/train_elastic.py --transport socket \
        --ranks 4 --procs 2
    PYTHONPATH=src python examples/train_elastic.py --transport socket \
        --ranks 4 --procs 2 --kill                                 # chaos
"""
import argparse
import os
import tempfile
import time

from repro import compile_cache, edat
from repro.runtime_dist.trainer import _demo_cfgs, trainer_program


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--transport", choices=("inproc", "socket"),
                    default="inproc")
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--procs", type=int, default=2,
                    help="processes to pack the ranks into (socket only)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--kill", action="store_true",
                    help="SIGKILL the last process after the first "
                         "checkpoint (socket only)")
    a = ap.parse_args()

    with tempfile.TemporaryDirectory(prefix="edat_train_example_") as td:
        ckdir = os.path.join(td, "ck")
        model_cfg, data_cfg, opt_cfg, trainer_cfg = _demo_cfgs(
            a.ranks, a.steps, ckdir, ckpt_every=3)

        if a.transport == "inproc":
            tr = trainer_program(model_cfg, data_cfg, opt_cfg, trainer_cfg)
            out = tr.run(timeout=600)
            hist = out["history"]
        else:
            from repro.checkpoint import latest_step
            with edat.Session(a.ranks, procs=a.procs, transport="socket",
                              timeout=600,
                              workers_per_rank=trainer_cfg.workers_per_rank,
                              unconsumed="ignore", hb_interval=0.2,
                              hb_timeout=1.5) as s:
                s.start(edat.deferred(trainer_program, model_cfg, data_cfg,
                                      opt_cfg, trainer_cfg))
                if a.kill:
                    deadline = time.monotonic() + 300
                    while ((latest_step(ckdir) or 0) < 3
                           and time.monotonic() < deadline):
                        if all(c is not None
                               for c in s.exitcodes().values()):
                            raise SystemExit(
                                "children exited before the first "
                                "checkpoint")
                        time.sleep(0.05)
                    if (latest_step(ckdir) or 0) < 3:
                        raise SystemExit("no checkpoint appeared within "
                                         "300s")
                    victim = a.ranks - 1
                    print(f"== SIGKILL the process hosting rank {victim} "
                          f"==")
                    s.kill(victim)
                s.wait(600, check=not a.kill)
                res = s.gather()
            hist = res["history"]
            for r in res["recoveries"]:
                print(f"rank {r['rank']}: rolled back to step {r['step']} "
                      f"(epoch {r['epoch']})")

        for m in hist:
            print(f"rank {m['rank']} step {m['step']:3d} "
                  f"loss {m['loss']:.4f} grads {m['n_grads']} "
                  f"stale {m['n_stale']}")
        print(f"reached step {max(m['step'] for m in hist)}/{a.steps} "
              f"({a.transport})")


if __name__ == "__main__":
    main()
