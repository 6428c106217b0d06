#!/usr/bin/env python3
"""Chip smoke test: the serving main path at full width on one TPU chip.

    python chip_smoke.py [--seed N]      # one chip
    python chip_smoke.py --four-chips    # four chips, one process each

One chip.  ``repro.serve.run_serve`` serves StableLM-2-1.6B at its
published widths (random weights from ``--seed``) on the in-proc
transport: 2 open-loop clients, 8 decode slots, a 1024-token cache, 16
requests with prompts of 32-256 tokens and 16-64 new tokens.  Checked:

* every request served, no leaked slot, one decode step per tick;
* every token in ``[0, vocab)``;
* each request's first token equals the sequential server's (both come
  from the same batch-1 prefill program, so they must be equal);
* logits on the serving engine's own programs agree with a float32 run
  on the host CPU (a child process with ``JAX_PLATFORMS=cpu``): the
  prefill of one prompt, and one batched decode step for that request
  sitting in slot ``PROBE_SLOT`` among ``SLOTS`` live requests of other
  lengths (its cache spliced in, as serving does).  Twice:
  - as served, in bf16.  bf16 itself moves random-weight logits far
    (about 0.25 relative RMS at 24 layers), so this bound is relative:
    at most ``LOGITS_SLACK`` times the distance of the same bf16 model
    run on the host CPU on the same tokens (and never less than
    ``LOGITS_FLOOR``).  It catches gross faults only;
  - the same engine and programs in float32 at the highest matmul
    precision, held to ``F32_BOUND`` relative RMS: float32 rounding,
    amplified as much as bf16's is, stays orders of magnitude below it,
    while one wrong layer or a bf16 computation lands far above;
* a control that must fail the float32 check does: the prompt's prefill
  with one layer's weights exchanged with the next layer's.  The same
  control under the bf16 bound is reported, not gated.

How often later tokens agree with the sequential server is reported, not
gated: the batch-8 and batch-1 decode programs round differently in
bf16, and with random weights near-ties flip greedy tokens.

Four chips.  The elastic data-parallel trainer runs at
``Session(ranks=4, procs=4, transport="socket")``, each process pinned
to its own chip by ``repro.net.launch``; each prints the chip it holds.
The reference is the same schedule in-proc, all four ranks as threads of
this process on one chip.  Final parameters must match the reference
within the tolerance ``tests/test_trainer_dist.py`` uses on the CPU: the
quorum folds gradients in rank order and every replica runs the same
programs on the same kind of chip, so they are expected to be identical.
The model is mamba2-370m at its published widths with its 48 layers cut
to ``TRAIN_LAYERS``, so the reference's four replicas with their AdamW
state fit one chip.

The last line of standard output is ``{"ok": true, "device": {...}}``;
any failed check exits non-zero before it is printed, and a platform
other than TPU is a failure.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

ARCH = "stablelm-1.6b"
CLIENTS, SLOTS, MAX_LEN, REQUESTS = 2, 8, 1024, 16
PROMPT_LENS = (32, 64, 128, 256)
LOGITS_SLACK, LOGITS_FLOOR = 2.0, 0.02
F32_BOUND, F32_MAX_LEN = 1e-2, 512
PROBE_SLOT = 5
TRAIN_ARCH, TRAIN_LAYERS, TRAIN_STEPS = "mamba2-370m", 12, 3


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts: Any) -> None:
    print("[chip_smoke]", *parts, flush=True)


def device_info() -> Dict[str, Any]:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_tpu() -> Dict[str, Any]:
    info = device_info()
    check(info["platform"] == "tpu",
          f"no TPU: JAX's first device is {info['platform']!r} "
          f"({info['kind']})")
    return info


def memory() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (f"bytes in use {stats.get('bytes_in_use')}, peak "
            f"{stats.get('peak_bytes_in_use')}")


class CompileClock:
    """Sums XLA backend compile time while registered."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, name: str, secs: float, **_: Any) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


# ------------------------------------------------------------------ config
def model_cfg(reduced: bool, max_len: int = MAX_LEN):
    from repro.configs import ARCHS, reduce_cfg
    from repro.serve import serving_cfg
    cfg = ARCHS[ARCH].cfg
    return serving_cfg(reduce_cfg(cfg) if reduced else cfg, max_len)


def smoke_load(seed: int, requests: int = REQUESTS):
    from repro.serve import LoadSpec
    return LoadSpec(rps=4.0, requests=requests, prompt_lens=PROMPT_LENS,
                    max_new_lo=16, max_new_hi=64, seed=seed)


def param_bytes(cfg) -> int:
    import jax
    from repro.models import build_model
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(build_model(cfg).abstract_params()))


# ------------------------------------------------------------------ phases
def serve_phase(*, reduced: bool, seed: int, requests: int = REQUESTS,
                max_len: int = MAX_LEN) -> Dict[str, Any]:
    """Full serving round through ``run_serve``; checks its invariants."""
    from repro.serve import run_serve
    out = run_serve(arch=ARCH, reduced=reduced, clients=CLIENTS,
                    slots=SLOTS, max_len=max_len,
                    load=smoke_load(seed, requests), transport="inproc",
                    timeout=900.0, seed=seed)
    res = out["result"]
    vocab = model_cfg(reduced, max_len).vocab
    check(res["served"] == requests,
          f"served {res['served']} of {requests} requests")
    check(res["slots_leaked"] == 0, f"{res['slots_leaked']} slots leaked")
    check(res["tick_execs"] == res["steps"],
          f"{res['tick_execs']} decode ticks ran {res['steps']} steps")
    bad = [t for r in res["records"] for t in r["tokens"]
           if not 0 <= t < vocab]
    check(not bad, f"{len(bad)} tokens outside [0, {vocab})")
    return out


def sequential_phase(*, reduced: bool, seed: int, requests: int = REQUESTS,
                     max_len: int = MAX_LEN) -> List[Dict[str, Any]]:
    """The same requests served one at a time, batch of one."""
    from repro.serve import all_requests, run_sequential
    cfg = model_cfg(reduced, max_len)
    reqs = all_requests(smoke_load(seed, requests), CLIENTS, cfg.vocab)
    return run_sequential(cfg, reqs, max_len=max_len, seed=seed,
                          realtime=False)


def compare_tokens(served: Sequence[Dict[str, Any]],
                   sequential: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """First-token matches (gated) and positionwise token agreement."""
    ref = {r["id"]: r["tokens"] for r in sequential}
    check(sorted(ref) == sorted(r["id"] for r in served),
          "the servers answered different request ids")
    first = sum(r["tokens"][0] == ref[r["id"]][0] for r in served)
    pairs = [(a, b) for r in served for a, b in zip(r["tokens"],
                                                    ref[r["id"]])]
    out = {"requests": len(served), "first_token_matches": first,
           "tokens_compared": len(pairs),
           "token_agreement": sum(a == b for a, b in pairs) / len(pairs)}
    check(first == len(served),
          f"first tokens match the sequential server for only {first} of "
          f"{len(served)} requests")
    return out


def probe_requests(seed: int, vocab: int) -> List[Dict[str, Any]]:
    """The first ``SLOTS`` requests of the load; the first is the probe."""
    from repro.serve import all_requests
    return all_requests(smoke_load(seed), CLIENTS, vocab)[:SLOTS]


def decode_input(prompt: Sequence[int]) -> int:
    """The token a probe slot decodes next (any token will do; this one
    is known before the chip has run)."""
    return int(prompt[0])


def reference_sequences(seed: int, vocab: int) -> List[List[int]]:
    """The token sequences whose last-position logits the CPU reference
    computes: the probe prompt (prefill) and the probe prompt followed by
    its decode input (one decode step)."""
    prompt = list(probe_requests(seed, vocab)[0]["prompt"])
    return [prompt, prompt + [decode_input(prompt)]]


def swap_layers(params, layer: int):
    """``params`` with the weights of layers ``layer`` and ``layer + 1``
    exchanged: the control, a model with a wrong layer.  Donates
    ``params``: the caller's tree is gone afterwards."""
    import jax
    paths = [p for p, _ in jax.tree_util.tree_leaves_with_path(params)
             if any(getattr(k, "key", "").startswith("seg") for k in p)]
    check(bool(paths), "no stacked layer weights to swap")

    def swap(tree):
        def one(path, a):
            if path not in paths:
                return a
            return a.at[layer].set(a[layer + 1]).at[layer + 1].set(a[layer])
        return jax.tree_util.tree_map_with_path(one, tree)
    return jax.jit(swap, donate_argnums=0)(params)


def logits_phase(*, reduced: bool, seed: int, max_len: int = MAX_LEN,
                 f32: bool = False) -> Dict[str, np.ndarray]:
    """Logits of the serving engine's own prefill and decode programs
    (weights ``model.init`` from ``seed``, as ``run_serve`` makes them;
    ``f32``: a float32 model at the highest matmul precision): the probe
    prompt's prefill; one decode step with the probe request in
    ``PROBE_SLOT`` among ``SLOTS`` live requests, each spliced in after
    its own prefill; and last the control, the probe prefill with two
    layers exchanged.  ``decode_tokens`` is what the serving decode step
    itself emitted for that batch, ``decode_argmax`` the argmax of the
    decode logits."""
    import jax
    import jax.numpy as jnp
    from repro.serve import ServeEngine
    cfg = model_cfg(reduced, max_len)
    ctx = contextlib.nullcontext()
    if f32:
        cfg = cfg.replace(dtype="float32")
        ctx = jax.default_matmul_precision("highest")
    reqs = probe_requests(seed, cfg.vocab)
    toks = {"tokens": jnp.asarray(np.asarray(reqs[0]["prompt"],
                                             np.int32)[None])}
    out = {}
    with ctx:
        eng = ServeEngine(cfg, slots=SLOTS, max_len=max_len, seed=seed)
        out["prefill"] = np.asarray(
            eng._prefill(eng.params, toks)[0][0, -1], np.float32)
        order = reqs[1:]
        order.insert(PROBE_SLOT, reqs[0])
        for slot, r in enumerate(order):
            _, pcache = eng.prefill(r["prompt"])
            eng.attach(slot, len(r["prompt"]), decode_input(r["prompt"]),
                       pcache)
            del pcache
        logits, _ = jax.jit(eng.model.decode_step)(
            eng.params, eng.caches, jnp.asarray(eng.tokens),
            jnp.asarray(eng.pos))
        out["decode"] = np.asarray(logits[PROBE_SLOT, -1], np.float32)
        out["decode_argmax"] = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        out["decode_tokens"] = np.asarray(eng.step(range(SLOTS)))
        del logits
        eng.caches = None
        bad = swap_layers(eng.params, (cfg.n_layers - 1) // 2)
        eng.params = None
        out["control"] = np.asarray(eng._prefill(bad, toks)[0][0, -1],
                                    np.float32)
    return out


def reference_logits(*, reduced: bool, seed: int,
                     seqs: Sequence[Sequence[int]],
                     dtype: str = "bfloat16") -> np.ndarray:
    """Last-position prefill logits of each of ``seqs``, one row per
    sequence.  ``bfloat16``: the serving weights (``model.init`` from
    ``seed``) and model; ``float32-cast``: those bf16 weights cast to
    float32 in a float32 model; ``float32``: a float32 model with its own
    ``model.init`` from ``seed``, as ``logits_phase(f32=True)`` makes it.
    Float32 runs use the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    from repro.train import make_prefill_step
    cfg = model_cfg(reduced)
    ctx = contextlib.nullcontext()
    if dtype == "float32":
        cfg = cfg.replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    if dtype == "float32-cast":
        model = build_model(cfg.replace(dtype="float32"))
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    if dtype != "bfloat16":
        ctx = jax.default_matmul_precision("highest")
    step = jax.jit(make_prefill_step(model))
    rows = []
    with ctx:
        for seq in seqs:
            toks = jnp.asarray(np.asarray(seq, np.int32)[None])
            logits, _ = step(params, {"tokens": toks})
            rows.append(np.asarray(logits[0, -1], np.float32))
    return np.stack(rows)


def start_cpu_reference(*, reduced: bool, seed: int,
                        seqs: Sequence[Sequence[int]],
                        workdir: str) -> subprocess.Popen:
    """Start the host-CPU logits of ``seqs`` (float32 references and the
    same bf16 model) in a child that never touches the chip."""
    with open(os.path.join(workdir, "seqs.json"), "w") as f:
        json.dump([list(map(int, q)) for q in seqs], f)
    cmd = [sys.executable, os.path.abspath(__file__), "--cpu-reference",
           workdir, "--seed", str(seed)] + (["--reduced"] if reduced else [])
    return subprocess.Popen(cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"})


def finish_cpu_reference(proc: subprocess.Popen,
                         workdir: str) -> Dict[str, np.ndarray]:
    try:
        rc = proc.wait(timeout=1200)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    check(rc == 0, f"the CPU reference exited with {rc}")
    with np.load(os.path.join(workdir, "ref.npz")) as z:
        return {k: z[k] for k in z.files}


REFERENCES = {"f32": "float32-cast", "bf16": "bfloat16", "f32_own": "float32"}


def cpu_reference_main(workdir: str, seed: int, reduced: bool) -> int:
    with open(os.path.join(workdir, "seqs.json")) as f:
        seqs = json.load(f)
    out = {k: reference_logits(reduced=reduced, seed=seed, seqs=seqs,
                               dtype=d) for k, d in REFERENCES.items()}
    np.savez(os.path.join(workdir, "ref.npz"), **out)
    return 0


def rel_rms(a: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def compare_logits(chip: np.ndarray, f32: np.ndarray, bound: float,
                   what: str) -> Dict[str, Any]:
    """The chip's logits against a float32 reference, within ``bound``
    relative RMS."""
    out = {"max_abs_diff": float(np.max(np.abs(chip - f32))),
           "max_abs_ref": float(np.max(np.abs(f32))),
           "rel_rms": rel_rms(chip, f32), "bound": bound,
           "argmax_equal": bool(np.argmax(chip) == np.argmax(f32))}
    check(bool(np.all(np.isfinite(chip))), f"non-finite {what} logits")
    check(out["rel_rms"] <= bound,
          f"{what} logits are {out['rel_rms']:.4g} (relative RMS) from the "
          f"float32 reference; the bound is {bound:.4g}")
    return out


def check_logits(bf16: Dict[str, np.ndarray], f32: Dict[str, np.ndarray],
                 ref: Dict[str, np.ndarray]) -> Dict[str, Dict[str, Any]]:
    """bf16 prefill and decode within what bf16 costs on the host CPU;
    float32 prefill and decode within ``F32_BOUND``; the float32 control
    outside it (the bf16 control is only measured)."""
    out = {}
    for i, what in enumerate(("prefill", "decode")):
        spread = rel_rms(ref["bf16"][i], ref["f32"][i])
        out[what] = compare_logits(
            bf16[what], ref["f32"][i],
            max(LOGITS_SLACK * spread, LOGITS_FLOOR), f"bf16 {what}")
        out[what]["cpu_bf16_rel_rms"] = spread
        out[f"f32 {what}"] = compare_logits(
            f32[what], ref["f32_own"][i], F32_BOUND, f"float32 {what}")
    ctl = rel_rms(f32["control"], ref["f32_own"][0])
    out["control"] = {"rel_rms": ctl, "bound": F32_BOUND,
                      "bf16_rel_rms": rel_rms(bf16["control"],
                                              ref["f32"][0]),
                      "bf16_bound": out["prefill"]["bound"]}
    check(ctl > F32_BOUND,
          f"the control (two layers exchanged) reads {ctl:.4g}, within the "
          f"float32 bound {F32_BOUND:.4g}: the check cannot tell it from a "
          f"sound run")
    return out


def say_logits(lg: Dict[str, Dict[str, Any]],
               bf16: Dict[str, np.ndarray], seqs: Sequence[Sequence[int]]
               ) -> None:
    for what, n in (("prefill", len(seqs[0])), ("decode", len(seqs[1]))):
        x, y = lg[what], lg[f"f32 {what}"]
        say(f"{what} logits ({n} tokens, vocab {bf16[what].size}): bf16 "
            f"relative RMS {x['rel_rms']} vs bound {x['bound']} (CPU bf16: "
            f"{x['cpu_bf16_rel_rms']}), max-abs-diff {x['max_abs_diff']} "
            f"(max |ref| {x['max_abs_ref']}), argmax equal "
            f"{x['argmax_equal']}; float32 relative RMS {y['rel_rms']} vs "
            f"bound {y['bound']}, max-abs-diff {y['max_abs_diff']}, argmax "
            f"equal {y['argmax_equal']}")
    c = lg["control"]
    say(f"control, layers exchanged: float32 relative RMS {c['rel_rms']} "
        f"(must exceed {c['bound']}); bf16 relative RMS {c['bf16_rel_rms']} "
        f"vs the bf16 bound {c['bf16_bound']} (not gated); the serving "
        f"decode step's tokens equal the bf16 decode logits' argmax in "
        f"{int(np.sum(bf16['decode_tokens'] == bf16['decode_argmax']))}/"
        f"{SLOTS} slots")


def one_chip(seed: int, info: Dict[str, Any]) -> Dict[str, Any]:
    import gc
    import jax
    cfg = model_cfg(reduced=False)
    say(f"device {info}")
    say(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads x {cfg.hd}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}, {cfg.dtype}; param bytes {param_bytes(cfg)}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        seqs = reference_sequences(seed, cfg.vocab)
        ref_proc = start_cpu_reference(reduced=False, seed=seed, seqs=seqs,
                                       workdir=workdir)
        try:
            t0 = time.monotonic()
            out = serve_phase(reduced=False, seed=seed)
            res, s = out["result"], out["summary"]
            say(f"serve: {res['served']} requests, {s['tokens']} tokens, "
                f"{res['steps']} decode steps, {res['prefills']} prefills, "
                f"slots {res['slots']}, max_len {MAX_LEN}; "
                f"{time.monotonic() - t0:.1f}s with compiles; "
                f"compile {clock.seconds:.1f}s in {clock.count} programs; "
                f"{memory()}")
            served = res["records"]
            del out, res
            gc.collect()
            seq = sequential_phase(reduced=False, seed=seed)
            tok = compare_tokens(served, seq)
            say(f"sequential: first tokens {tok['first_token_matches']}/"
                f"{tok['requests']} equal; token agreement "
                f"{tok['token_agreement']} over {tok['tokens_compared']} "
                f"positions (reported, not gated); {memory()}")
            del seq
            gc.collect()
            bf16 = logits_phase(reduced=False, seed=seed)
            gc.collect()
            f32 = logits_phase(reduced=False, seed=seed,
                               max_len=F32_MAX_LEN, f32=True)
        except BaseException:
            ref_proc.kill()
            raise
        ref = finish_cpu_reference(ref_proc, workdir)
    lg = check_logits(bf16, f32, ref)
    say_logits(lg, bf16, seqs)
    say(f"compile {clock.seconds:.1f}s in {clock.count} programs; "
        f"{memory()}")
    return info


# -------------------------------------------------------------- four chips
def trainer_cfgs(seed: int, *, layers: int = TRAIN_LAYERS,
                 steps: int = TRAIN_STEPS, reduced: bool = False):
    from repro.configs import ARCHS, reduce_cfg
    from repro.data import DataCfg
    from repro.optim import OptCfg
    from repro.runtime_dist import TrainerCfg
    cfg = ARCHS[TRAIN_ARCH].cfg
    cfg = reduce_cfg(cfg) if reduced else cfg.replace(n_layers=layers)
    data = DataCfg(vocab=cfg.vocab, seq=256, global_batch=8, seed=seed)
    opt = OptCfg(name="adamw", peak_lr=1e-3, warmup=2, total_steps=100)
    trainer = TrainerCfg(steps=steps, n_ranks=4, collect_timeout=900.0,
                         seed=seed)
    return cfg, data, opt, trainer


def open_chip_files() -> List[str]:
    """The TPU device files this process holds open: which physical chip
    it drives, where JAX numbers its one visible chip 0 in every process."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if (target.startswith("/dev/accel")
                or target.removeprefix("/dev/vfio/").isdigit()):
            held.add(target)
    return sorted(held)


def pinned_trainer(model_cfg, data_cfg, opt_cfg, trainer_cfg, tpu: bool):
    """Program factory run in each rank process: reports the chip the
    process holds, then builds the trainer."""
    import jax
    from repro.runtime_dist import trainer_program
    t0 = time.time()
    d = jax.devices()
    say(f"rank process {os.environ.get('EDAT_LOCAL_RANKS')}: "
        f"{len(d)} device(s), id {d[0].id}, {d[0].platform} "
        f"{d[0].device_kind}, TPU_VISIBLE_CHIPS="
        f"{os.environ.get('TPU_VISIBLE_CHIPS')}, device files "
        f"{open_chip_files()}; backend start from wall time {t0:.3f} "
        f"took {time.time() - t0:.3f}s")
    check(not tpu or d[0].platform == "tpu",
          f"rank process on {d[0].platform}, not a TPU")
    return trainer_program(model_cfg, data_cfg, opt_cfg, trainer_cfg)


def four_chip_phase(seed: int, *, tpu: bool = True,
                    **cfg_kw: Any) -> Dict[str, Any]:
    """Socket trainer, one process per chip, then the in-proc reference
    here.  This process must not start JAX before the round is over."""
    from repro import edat
    from repro.models import build_model
    from repro.runtime_dist import EventDrivenTrainer, flatten_params
    cfgs = trainer_cfgs(seed, **cfg_kw)
    model_cfg_, data, opt, trainer = cfgs
    t0, wall0 = time.monotonic(), time.time()
    with edat.Session(4, procs=4, transport="socket", timeout=1000.0,
                      workers_per_rank=trainer.workers_per_rank,
                      unconsumed="ignore") as s:
        s.run(edat.deferred(pinned_trainer, *cfgs, tpu))
        res = s.gather()
        net = s.stats()["transport"]
    t_dist = time.monotonic() - t0
    # the socket transport's own log of each rank process: when its
    # heartbeat loop was frozen, and when it had told its peers it may be
    def since(x):
        return None if x is None else round(x - wall0, 3)
    say("heartbeat-loop stalls by rank process (seconds after the round "
        "started, seconds frozen): "
        + str({p: [(since(t), round(x, 3)) for t, x in log]
               for p, log in net.get("hb_stalls", {}).items()}))
    say("quiet windows by rank process (seconds after the round started): "
        + str({p: [(since(a), since(b)) for a, b in log]
               for p, log in net.get("quiet", {}).items()}))
    say("socket round (rank, step, gradients folded): "
        f"{[(m['rank'], m['step'], m['n_grads']) for m in res['history']]}")
    check(sorted(res["final_params"]) == [0, 1, 2, 3],
          f"final params from ranks {sorted(res['final_params'])}")
    check(all(m["n_grads"] == 4 for m in res["history"]),
          "a step ran without all four replicas' gradients")
    info = require_tpu() if tpu else device_info()
    t0 = time.monotonic()
    out = EventDrivenTrainer(build_model(model_cfg_), data, opt,
                             trainer).run(timeout=1000.0)
    t_ref = time.monotonic() - t0
    ref = {k: np.asarray(v, np.float32)
           for k, v in flatten_params(out["final_params"][0]).items()}
    worst = 0.0
    for r in range(4):
        got = res["final_params"][r]
        check(sorted(got) == sorted(ref), f"rank {r}: parameter names differ")
        for k, v in got.items():
            v = np.asarray(v, np.float32)
            worst = max(worst, float(np.max(np.abs(v - ref[k]))))
            check(np.allclose(v, ref[k], rtol=1e-5, atol=1e-6),
                  f"rank {r} {k}: max |diff| "
                  f"{float(np.max(np.abs(v - ref[k])))}")
    n = sum(v.size for v in ref.values())
    say(f"four chips: {model_cfg_.name} ({model_cfg_.n_layers} layers, "
        f"d_model {model_cfg_.d_model}, {n} params), {trainer.steps} "
        f"steps; socket round {t_dist:.1f}s, in-proc reference "
        f"{t_ref:.1f}s; rank-0 losses "
        f"{[m['loss'] for m in res['history'] if m['rank'] == 0]}; "
        f"max |param diff| vs reference {worst} (rtol 1e-5, atol 1e-6)")
    return info


# --------------------------------------------------------------------- main
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip trainer path and its "
                         "in-proc reference")
    ap.add_argument("--cpu-reference", metavar="DIR", help=argparse.SUPPRESS)
    ap.add_argument("--reduced", action="store_true", help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if a.cpu_reference:
        return cpu_reference_main(a.cpu_reference, a.seed, a.reduced)
    from repro import compile_cache
    try:
        if a.four_chips:
            # the rank processes take the chips: no JAX here before them
            compile_cache.enable()
            info = four_chip_phase(a.seed)
        else:
            info = require_tpu()
            compile_cache.enable()
            info = one_chip(a.seed, info)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
