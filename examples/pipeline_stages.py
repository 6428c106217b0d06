"""Host-level pipeline parallelism expressed as EDAT events.

Each rank is a pipeline stage owning a parameter slice.  Microbatches flow
forward as ``acts`` events and backward as ``grads`` events; a stage works
on whichever event arrives next, so the 1F1B interleave *emerges* from
event arrival order instead of a globally scheduled timetable — the
paper's thesis (drive interactions with events, no explicit
synchronisation) applied to pipeline training.  In-program (pjit) sharding
handles DP/TP inside each stage on a real pod; events carry inter-stage
activations across hosts.

  PYTHONPATH=src python examples/pipeline_stages.py --stages 3 --microbatches 8
"""
import argparse
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, edat


def make_stage_fns(d, layers_per_stage, last):
    """Each stage: a small MLP block; last stage adds the loss."""

    def fwd(params, x):
        for w in params:
            x = jnp.tanh(x @ w)
        return x

    def loss_fn(params, x, y):
        out = fwd(params, x)
        return jnp.mean((out - y) ** 2)

    if last:
        grad_x_and_p = jax.jit(jax.grad(loss_fn, argnums=(0, 1)))
        loss_val = jax.jit(loss_fn)
        return jax.jit(fwd), grad_x_and_p, loss_val
    vjp_fwd = jax.jit(lambda p, x, g: jax.vjp(fwd, p, x)[1](g))
    return jax.jit(fwd), vjp_fwd, None


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=3)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--layers-per-stage", type=int, default=2)
    ap.add_argument("--mb-size", type=int, default=16)
    args = ap.parse_args()

    S, M, d = args.stages, args.microbatches, args.width
    rng = np.random.default_rng(0)
    losses = []
    mu = threading.Lock()

    # fixed regression task
    X = rng.standard_normal((args.steps, M, args.mb_size, d)).astype(
        np.float32)
    W_true = rng.standard_normal((d, d)).astype(np.float32) / np.sqrt(d)
    Y = np.tanh(X @ W_true)

    state = [None] * S  # per-stage params + stash

    def main_fn(ctx):
        r = ctx.rank
        last = r == S - 1
        key = jax.random.PRNGKey(r)
        params = [jax.random.normal(jax.random.fold_in(key, i), (d, d)) /
                  np.sqrt(d) for i in range(args.layers_per_stage)]
        fwd, bwd, lossf = make_stage_fns(d, args.layers_per_stage, last)
        stash = {}
        gacc = [jnp.zeros_like(w) for w in params]
        done_mb = [0]
        step = [0]
        lr = 0.05

        def maybe_finish_step(ctx2):
            if done_mb[0] == M:
                # local optimizer update, then a non-blocking barrier
                # (paper Listing 6) before the next step's microbatches
                for i, w in enumerate(params):
                    params[i] = w - lr * gacc[i] / M
                    gacc[i] = jnp.zeros_like(w)
                done_mb[0] = 0
                step[0] += 1
                ctx2.fire(edat.ALL, "step_done")

        def on_acts(ctx2, events):
            mb, x = events[0].data
            x = jnp.asarray(x)
            if last:
                y = jnp.asarray(Y[step[0], mb])
                (gp, gx) = bwd(params, x, y)
                with mu:
                    losses.append(float(lossf(params, x, y)))
                for i, g in enumerate(gp):
                    gacc[i] = gacc[i] + g
                ctx2.fire(r - 1, "grads", (mb, np.asarray(gx)))
                done_mb[0] += 1
                maybe_finish_step(ctx2)
            else:
                out = fwd(params, x)
                stash[mb] = x
                ctx2.fire(r + 1, "acts", (mb, np.asarray(out)))

        def on_grads(ctx2, events):
            mb, g = events[0].data
            x = stash.pop(mb)
            gp, gx = bwd(params, x, jnp.asarray(g))
            for i, gi in enumerate(gp):
                gacc[i] = gacc[i] + gi
            if r > 0:
                ctx2.fire(r - 1, "grads", (mb, np.asarray(gx)))
            done_mb[0] += 1
            maybe_finish_step(ctx2)

        def feeder(ctx2, events):
            # stage 0 injects the next step's microbatches after the
            # all-stage barrier
            if step[0] >= args.steps:
                return
            for mb in range(M):
                ctx2.fire(0 if r == 0 else r, "acts",
                          (mb, X[step[0], mb]))

        ctx.submit_persistent(on_acts, deps=[(edat.ANY, "acts")],
                              name="fwd")
        if not last:
            ctx.submit_persistent(on_grads, deps=[(edat.ANY, "grads")],
                                  name="bwd")
        if r == 0:
            ctx.submit_persistent(feeder, deps=[(edat.ALL, "step_done")],
                                  name="feeder")
            feeder(ctx, [])   # kick off step 0
        state[r] = params

    t0 = time.monotonic()
    edat.run(main_fn, ranks=S, workers_per_rank=1,
             unconsumed="ignore", timeout=600)
    dt = time.monotonic() - t0
    per_step = [np.mean(losses[i * M:(i + 1) * M])
                for i in range(args.steps)]
    print(f"pipeline {S} stages x {M} microbatches, {args.steps} steps "
          f"in {dt:.2f}s")
    print("  per-step loss:", " ".join(f"{l:.4f}" for l in per_step))
    assert per_step[-1] < per_step[0], "pipeline training must reduce loss"


if __name__ == "__main__":
    main()
