"""Elastic-trainer throughput: steps/s and grad-events/s over rank counts,
in-proc (threads-as-ranks) vs distributed (OS processes over the
coalescing SocketTransport, several ranks per process).

``--transport socket`` runs the same trainer program through a socket
``edat.Session`` — SPMD across spawned processes, co-located ranks
exchanging gradients in-process (zero socket frames) and remote ranks
over the wire.  Each row records:

* ``steps_per_s``        — global optimiser steps per second of (in-child)
  run time, first-JIT included (both transports pay it, so A/B holds);
* ``grad_events_per_s``  — gradient events *consumed* per second, summed
  over every rank's quorum collections (``n_grads + n_stale`` per
  recorded step) — the trainer-level event rate the coalescing fast
  path feeds;
* ``loss_first``/``loss_last`` — sanity that the thing actually trains.

In-proc rows run in a process of their own and this process never
starts JAX: a chip belongs to one process at a time, and the socket
rows' rank processes need it.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.net.launch import run_in_process


def _row_from_history(history, steps, wall, label, ranks, procs):
    grads = sum(m["n_grads"] + m["n_stale"] for m in history)
    loss_first = float(np.mean([m["loss"] for m in history
                                if m["step"] <= 2] or [np.nan]))
    loss_last = float(np.mean([m["loss"] for m in history
                               if m["step"] >= steps - 1] or [np.nan]))
    row = {"impl": label, "ranks": ranks, "procs": procs,
           "wall_s": wall, "steps_per_s": steps / max(wall, 1e-9),
           "grad_events_per_s": grads / max(wall, 1e-9),
           "loss_first": loss_first, "loss_last": loss_last}
    print(f"  trainer {label:12s} ranks={ranks} procs={procs} "
          f"steps/s={row['steps_per_s']:7.2f} "
          f"grad-ev/s={row['grad_events_per_s']:8.1f} "
          f"loss {loss_first:.3f}->{loss_last:.3f}")
    return row


def _inproc_run(n_ranks: int, steps: int):
    """One in-proc (threads-as-ranks) round: ``(history, wall seconds)``."""
    from repro.models import build_model
    from repro.runtime_dist import EventDrivenTrainer
    from repro.runtime_dist.trainer import _demo_cfgs
    model_cfg, data_cfg, opt_cfg, trainer_cfg = _demo_cfgs(
        n_ranks, steps, ckpt_dir=None)
    tr = EventDrivenTrainer(build_model(model_cfg), data_cfg, opt_cfg,
                            trainer_cfg)
    t0 = time.monotonic()
    out = tr.run(timeout=600.0)
    return out["history"], time.monotonic() - t0


def run(steps: int = 12, ranks=(1, 2, 4), transport: str = "inproc",
        procs=None, out: str = None):
    assert transport in ("inproc", "socket")
    from repro.runtime_dist.trainer import _demo_cfgs

    rows = []
    for nr in ranks:
        model_cfg, data_cfg, opt_cfg, trainer_cfg = _demo_cfgs(
            nr, steps, ckpt_dir=None)
        if transport == "socket":
            from repro import edat
            from repro.runtime_dist import trainer_program
            np_ = min(procs or max(1, nr // 2), nr)
            with edat.Session(nr, procs=np_, transport="socket",
                              timeout=600.0, unconsumed="ignore",
                              workers_per_rank=trainer_cfg.workers_per_rank
                              ) as s:
                s.run(edat.deferred(trainer_program, model_cfg, data_cfg,
                                    opt_cfg, trainer_cfg))
                res = s.gather()
                wall = float(s.stats.get("run_seconds", 0.0))
            rows.append(_row_from_history(res["history"], steps, wall,
                                          "edat-socket", nr, np_))
        else:
            history, wall = run_in_process(_inproc_run, nr, steps)
            rows.append(_row_from_history(history, steps, wall,
                                          "edat-inproc", nr, 1))
    result = {"steps": steps, "transport": transport, "rows": rows}
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
    ap.add_argument("--transport", choices=("inproc", "socket"),
                    default="inproc")
    ap.add_argument("--ranks", type=int, nargs="+", default=None,
                    help="rank counts to sweep (default: 1 2 4 inproc, "
                         "2 4 socket)")
    ap.add_argument("--procs", type=int, default=None,
                    help="processes for socket runs (default ranks//2)")
    ap.add_argument("--steps", type=int, default=12)
    a = ap.parse_args()
    ranks = tuple(a.ranks) if a.ranks else (
        (2, 4) if a.transport == "socket" else (1, 2, 4))
    run(steps=a.steps, ranks=ranks, transport=a.transport, procs=a.procs,
        out=a.out)
