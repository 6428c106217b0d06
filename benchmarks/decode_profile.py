#!/usr/bin/env python3
"""Op-level profile of the serving engine's decode step on the chip.

    python3 benchmarks/decode_profile.py --arch stablelm-1.6b --slots 16 \\
        --max-len 1024 --steps 8 --out chiprun_out/decode_profile.json
    python3 benchmarks/decode_profile.py \\
        --config benchmarks/chip/configs/longcat-flash-chat.json \\
        --slots 64 --max-len 2048 --pos 1024

Builds the architecture at its published widths (``serving_cfg``: the
engine's own), or as a chip benchmark configuration file cuts it
(``--config``, read as the benchmark's harness reads it), random
weights made on the device from ``--seed``, a cache
of ``--slots`` rows of ``--max-len`` with every slot at ``--pos``, and
``jax.jit(make_serve_step(model))`` as ``ServeEngine`` jits it.  After two
warm calls it traces ``--steps`` steps with the JAX profiler, each ended
by ``block_until_ready``, and reduces the device plane's ``XLA Ops`` line
to the time per step of each operation, with its opcode, result shape and
the bytes of that result (unpadded) from the compiled HLO.  Prints the top
operations and the time per step under each named scope of ``SCOPES``
(the first that an operation's ``op_name`` holds); writes them all to
``--out`` as JSON.  Needs a TPU: on any other platform it
exits with code 2.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

OPS, MODULES = "XLA Ops", "XLA Modules"
STEP = "serve_step"
NESTING = ("while", "conditional", "call")
#: named scopes of the model's layers, most specific first
SCOPES = ("moe.router", "moe.experts", "moe.zero", "mla", "mlp")
_INST = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+?)(?:\{[^}]*\})? ([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NEST = re.compile(r"^\s*(?:ROOT )?%(\S+) = \(.*\) (while|conditional|call)\(")


def shape_bytes(shape: str) -> int:
    """Bytes of an array shape such as ``bf16[16,1024,32,64]``; 0 for a
    tuple or a shape it cannot read."""
    m = re.fullmatch(r"([a-z]+)(\d+)\[([\d,]*)\]", shape)
    if not m:
        return 0
    n = 1
    for d in filter(None, m.group(3).split(",")):
        n *= int(d)
    return n * int(m.group(2)) // 8


def scope_of(op_name: str) -> str:
    """The first of ``SCOPES`` on the path ``op_name``, else ``other``."""
    parts = op_name.split("/")
    return next((s for s in SCOPES if s in parts), "other")


def hlo_ops(text: str) -> dict:
    """``{instruction name: (result shape, opcode, scope)}`` from HLO
    text (a nested computation's scope is ``other``)."""
    out = {}
    for line in text.splitlines():
        m = _INST.match(line)
        if m:
            name = _OP_NAME.search(line)
            out[m.group(1)] = (m.group(2), m.group(3),
                               scope_of(name.group(1) if name else ""))
        elif _NEST.match(line):
            out[_NEST.match(line).group(1)] = ("(tuple)",
                                               _NEST.match(line).group(2),
                                               "other")
    return out


def reduce_ops(trace_dir: str, steps: int) -> tuple:
    """Per-step device time of each operation of the decode program, and
    the program's own time per step (ms)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    data = ProfileData.from_file(path)
    ops: dict = {}
    module_ns = 0
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == MODULES:
                module_ns += sum(e.duration_ns for e in line.events
                                 if STEP in e.name)
            if line.name != OPS:
                continue
            for e in line.events:
                # the event's name is the instruction's HLO text or its name
                name = e.name.split(" = ", 1)[0].lstrip("%")
                op = ops.setdefault(name, {"ns": 0.0, "n": 0})
                op["ns"] += e.duration_ns
                op["n"] += 1
    for op in ops.values():
        op["ms_per_step"] = op.pop("ns") / steps / 1e6
        op["per_step"] = op.pop("n") / steps
    return ops, module_ns / steps / 1e6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--config", default="",
                    help="a chip benchmark configuration file (it names "
                         "its architecture and cuts it; --arch is then "
                         "not read)")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--pos", type=int, default=512)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: the default device is {dev.platform}", file=sys.stderr)
        return 2
    from repro import compile_cache
    from repro.configs import ARCHS
    from repro.models import build_model
    from repro.serve.engine import serving_cfg
    from repro.train import make_serve_step
    compile_cache.enable()

    if args.config:
        from chip.harness import program_cfg
        with open(args.config) as f:
            cfg = program_cfg(json.load(f))
    else:
        cfg = ARCHS[args.arch].cfg
    model = build_model(serving_cfg(cfg, args.max_len))
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed % 2**31))
    caches = model.init_cache(args.slots, args.max_len)
    tokens = jnp.zeros((args.slots, 1), jnp.int32)
    pos = jnp.full((args.slots, 1), args.pos, jnp.int32)
    step = jax.jit(make_serve_step(model))
    compiled = step.lower(params, caches, tokens, pos).compile()
    shapes = hlo_ops(compiled.as_text())
    for _ in range(2):
        tokens, caches, _ = step(params, caches, tokens, pos)
    jax.block_until_ready(caches)

    host = []
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(args.steps):
                t0 = time.perf_counter()
                tokens, caches, _ = step(params, caches, tokens, pos)
                jax.block_until_ready((tokens, caches))
                host.append((time.perf_counter() - t0) * 1e3)
        ops, module_ms = reduce_ops(trace_dir, args.steps)
    if not ops:
        print("no XLA Ops events on /device:TPU:0", file=sys.stderr)
        return 1
    for name, op in ops.items():
        op["shape"], op["opcode"], op["scope"] = shapes.get(name,
                                                           ("", "", "other"))
        op["result_bytes"] = shape_bytes(op["shape"])
    scopes = {s: 0.0 for s in SCOPES + ("other",)}
    for op in ops.values():
        if op["opcode"] not in NESTING:
            scopes[op["scope"]] += op["ms_per_step"]
    # a loop's own event spans the operations of its body
    total = sum(op["ms_per_step"] for op in ops.values()
                if op["opcode"] not in NESTING)
    top = sorted(ops.items(), key=lambda kv: -kv[1]["ms_per_step"])
    print(f"{cfg.name} slots={args.slots} max_len={args.max_len} "
          f"pos={args.pos} device={dev.device_kind}: step {module_ms:.3f} ms "
          f"on the device (ops {total:.3f} ms), host "
          f"{sorted(host)[len(host) // 2]:.3f} ms median")
    print("by scope: " + ", ".join(f"{s} {ms:.3f} ms"
                                   for s, ms in scopes.items()))
    for name, op in top[:args.top]:
        print(f"{op['ms_per_step']:9.3f} ms {100 * op['ms_per_step'] / total:5.1f}%"
              f" x{op['per_step']:<6g} {op['opcode']:<22} {name:<40} "
              f"{op['shape'][:40]:<40} {op['result_bytes'] / 1e6:10.3f} MB")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"arch": cfg.name, "slots": args.slots,
                       "max_len": args.max_len, "pos": args.pos,
                       "device": dev.device_kind, "module_ms": module_ms,
                       "ops_ms": total, "host_ms": host,
                       "scopes_ms": scopes,
                       "ops": dict(top)}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
