"""The compute half of the serving subsystem: batched decode slots with a
per-slot KV-cache lifecycle.

:class:`ServeEngine` owns the model, its parameters, and one decode cache
of ``slots`` batch rows.  The two operations the event layer drives:

* :meth:`prefill` — run the prompt through the model's prefill path into
  a *fresh single-request cache* (length ``max_len``, so its per-layer
  shapes match one slot of the batch cache) and return the first greedy
  token plus that cache.  This is the long, prompt-length-dependent
  phase; it touches no shared decode state, so the event layer runs it
  concurrently with decode ticks.
* :meth:`attach` / :meth:`step` — splice a prefilled cache into a batch
  slot and advance the whole batch one greedy token.  ``attach``
  overwrites *every* cache leaf of the slot (K/V pages, cache position
  markers, recurrent states), which is what makes slot reuse safe: a
  freed slot's stale attention state can never leak into the next
  request admitted there.  ``step`` advances position counters only for
  the slots listed live — a dead slot's position stays pinned instead of
  marching unboundedly toward the cache end.

Both fixes are load-bearing (see ``tests/test_serve.py`` regressions):
the demo this subsystem replaced reused slots without resetting the KV
cache — a new request decoded against the previous occupant's attention
state — and advanced ``pos`` for dead slots on every tick.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import edat
from repro.models import build_model
from repro.train import make_prefill_step, make_serve_step

DEFAULT_MAX_LEN = 128


def serving_cfg(cfg, max_len: int = DEFAULT_MAX_LEN):
    """Normalize a model config for token-in/token-out serving: no
    multimodal frontend, decoder-only, cache length ``max_len``."""
    return cfg.replace(frontend="none", n_frontend_tokens=0, encdec=False,
                       max_target_length=max_len)


def _make_splice(model, slots: int):
    """jitted ``splice(caches, pcache, slot) -> caches`` writing the
    single-request cache ``pcache`` over batch row ``slot`` of every
    cache leaf.  Stacked-layer segments carry a leading ``layers`` dim
    (``stack_spec``), so the batch axis is per-segment: 1 when the
    segment is a scan-over-layers stack, else 0."""
    reps = [r for (_, r) in model.segments]

    def splice(caches, pcache, slot):
        out = []
        for seg, pseg, rep in zip(caches, pcache, reps):
            axis = 1 if rep > 1 else 0

            def put(c, p, axis=axis):
                shp = [1] * c.ndim
                shp[axis] = c.shape[axis]
                mask = (jnp.arange(c.shape[axis]) == slot).reshape(shp)
                return jnp.where(mask, p, c)

            out.append(jax.tree.map(put, seg, pseg))
        return out

    return jax.jit(splice)


class ServeEngine:
    """Model + batched decode state for one serving process."""

    def __init__(self, cfg, *, slots: int, max_len: int = DEFAULT_MAX_LEN,
                 seed: int = 0):
        cfg = serving_cfg(cfg, max_len)
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.model = build_model(cfg)
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self._decode = jax.jit(make_serve_step(self.model))
        # one jit; XLA re-specializes per distinct prompt length (the
        # loadgen draws lengths from a few buckets to bound compiles)
        self._prefill = jax.jit(make_prefill_step(self.model,
                                                  max_len=max_len))
        self._splice = _make_splice(self.model, slots)
        self.caches = self.model.init_cache(slots, max_len)
        self.tokens = np.zeros((slots, 1), np.int32)
        self.pos = np.zeros((slots, 1), np.int32)
        #: decode-step invocation counter — the single-chain regression
        #: test asserts tick executions == steps exactly
        self.step_count = 0
        self.prefill_count = 0
        #: live rows' expert choices (held experts, identity experts) in
        #: decode steps, summed over steps and layers; zeros where no
        #: layer routes
        self.moe_rows = np.zeros(2, np.int64)

    # ----------------------------------------------------------- prefill
    def clip_max_new(self, prompt_len: int, max_new: int) -> int:
        """Bound a request's output so prompt + output fits the cache."""
        return max(1, min(max_new, self.max_len - prompt_len))

    def prefill(self, prompt: Sequence[int]) -> Tuple[int, Any]:
        """Prompt -> (first greedy token, fresh single-request cache).
        Shared-state free: safe to run outside the server lock."""
        toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
        logits, pcache = self._prefill(self.params, {"tokens": toks})
        self.prefill_count += 1
        return int(jnp.argmax(logits[:, -1], axis=-1)[0]), pcache

    def warmup(self, prompt_lens: Sequence[int] = ()) -> None:
        """Pay the XLA compiles (decode step + one prefill per prompt
        bucket) up front, then reset all decode state and counters —
        so serving-latency measurements never include compile time."""
        for plen in sorted(set(prompt_lens)):
            self.prefill([0] * int(plen))
        self.step([])
        self.caches = self.model.init_cache(self.slots, self.max_len)
        self.tokens[:] = 0
        self.pos[:] = 0
        self.step_count = 0
        self.prefill_count = 0
        self.moe_rows[:] = 0

    # ------------------------------------------------------------ decode
    def attach(self, slot: int, prompt_len: int, first_token: int,
               pcache: Any) -> None:
        """Splice a prefilled request into ``slot``: the whole slot is
        overwritten (KV pages, pos markers, recurrent state) — the
        per-slot cache reset on admit."""
        self.caches = self._splice(self.caches, pcache, slot)
        self.tokens[slot, 0] = first_token
        self.pos[slot, 0] = prompt_len

    def step(self, live: Sequence[int]) -> np.ndarray:
        """One greedy decode step over the whole batch; returns the
        next-token column (``(slots,)``).  Tokens/positions advance only
        for ``live`` slots — dead rows keep stepping through the jitted
        batch (their output is ignored) but their position is pinned, so
        an idle slot never walks its write pointer to ``max_len``.

        Traced, it records ``engine.step`` with two children:
        ``engine.step.launch`` (the uploads of tokens and positions and the
        jitted call returning) and ``engine.step.read`` (the token read,
        which waits for the device).  A model with held experts also
        returns each row's expert choices, read with the tokens; the live
        rows' are added to ``moe_rows`` and set on ``engine.step`` as
        ``moe_held_rows`` and ``moe_zero_rows``."""
        with edat.span("engine.step") as sp:
            with edat.span("engine.step.launch"):
                nxt, self.caches, rows = self._decode(
                    self.params, self.caches, jnp.asarray(self.tokens),
                    jnp.asarray(self.pos))
            self.step_count += 1
            with edat.span("engine.step.read"):
                out, rows = jax.device_get((nxt, rows))
            if rows is not None:
                rows = rows[list(live)].sum(axis=0)
                self.moe_rows += rows
                sp.set(moe_held_rows=int(rows[0]), moe_zero_rows=int(rows[1]))
            for i in live:
                self.tokens[i, 0] = out[i, 0]
                self.pos[i, 0] += 1
            return out[:, 0]


class SequentialEngine:
    """The naive baseline: one request at a time, batch of one, prefill
    then decode to completion — no continuous batching, no overlap.
    Identical math to :class:`ServeEngine` (same builders, same greedy
    argmax), so the event-driven server's tokens must match this
    baseline's token-for-token."""

    def __init__(self, cfg, *, max_len: int = DEFAULT_MAX_LEN,
                 seed: int = 0):
        self._eng = ServeEngine(cfg, slots=1, max_len=max_len, seed=seed)

    @property
    def step_count(self) -> int:
        return self._eng.step_count

    def warmup(self, prompt_lens: Sequence[int] = ()) -> None:
        self._eng.warmup(prompt_lens)

    def serve_one(self, prompt: Sequence[int],
                  max_new: int) -> Tuple[List[int], float, float]:
        """Serve one request to completion; returns ``(tokens, t_first,
        t_done)`` with the same greedy tokens the batched engine emits
        for this prompt."""
        eng = self._eng
        max_new = eng.clip_max_new(len(prompt), max_new)
        first, pcache = eng.prefill(prompt)
        t_first = time.monotonic()
        eng.caches = pcache          # batch of one: the cache IS the slot
        eng.tokens[0, 0] = first
        eng.pos[0, 0] = len(prompt)
        out = [first]
        for _ in range(max_new - 1):
            out.append(int(eng.step([0])[0]))
        return out, t_first, time.monotonic()
