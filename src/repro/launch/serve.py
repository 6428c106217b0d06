"""Production serving launcher: prefill + decode steps on the host mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --batch 4 --prompt-len 16 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS, reduce_cfg
    from repro.models import build_model
    from repro.train import make_serve_step

    spec = ARCHS[args.arch]
    cfg = reduce_cfg(spec.cfg) if args.reduced else spec.cfg
    if cfg.frontend != "none" or cfg.encdec:
        cfg = cfg.replace(frontend="none", n_frontend_tokens=0,
                          encdec=False)
    total = args.prompt_len + args.max_new
    cfg = cfg.replace(max_target_length=max(cfg.max_target_length, total))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B = args.batch
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (B, args.prompt_len), 0, cfg.vocab)
    caches = model.init_cache(B, total)
    t0 = time.monotonic()
    logits, caches = jax.jit(model.prefill)(params, tokens, caches)
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    t_prefill = time.monotonic() - t0
    serve_step = jax.jit(make_serve_step(model))
    pos = jnp.full((B, 1), args.prompt_len, jnp.int32)
    out = [nxt]
    t0 = time.monotonic()
    for i in range(args.max_new - 1):
        nxt, caches, _ = serve_step(params, caches, nxt, pos)
        pos = pos + 1
        out.append(nxt)
    dt = time.monotonic() - t0
    toks = B * (args.max_new - 1)
    print(f"{args.arch}: prefill({B}x{args.prompt_len}) {t_prefill:.2f}s; "
          f"decode {toks} tokens in {dt:.2f}s ({toks/max(dt,1e-9):.1f} "
          f"tok/s incl. compile)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
