"""The harness: one cell of ``BENCHMARK.json`` served through the program's
own path, with the benchmark's timestamps and spans around the engine.

The window drives ``repro.serve.ServeProgram`` on an in-proc
``edat.Session``: server rank 0 and ``clients`` load ranks, as
``repro.serve.run_serve`` builds it.  The load ranks replay the
benchmark's schedule (``traffic.py``) instead of the program's load
generator; everything on the server rank is the program's.  The engine's
``prefill``, ``attach`` and ``step`` are wrapped, per instance, with host
timestamps and ``jax.profiler.TraceAnnotation`` spans (``bench.prefill#i``
and so on) that the metric readers and the trace reduction use.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import json
import os
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import reference, stalls, traffic, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: seconds of the window the traced run records, at its end
TRACE_SECONDS = 3.0
#: seconds a run may take to drain after its window, at most
DRAIN_TIMEOUT = 240.0


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ------------------------------------------------------------------ the cell
@dataclasses.dataclass
class Cell:
    """One workload: its configuration, traffic, metrics and limits."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, Any]

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]

    @property
    def serving(self) -> Dict[str, Any]:
        return self.config["serving"]

    @classmethod
    def load(cls, workload: str, root: str = ROOT) -> "Cell":
        bench = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        w = cells[workload]

        def here(m):
            return m.get("workloads") is None or workload in m["workloads"]

        return cls(name=workload, chips=int(w["chips"]),
                   config=load_json(HERE, "configs", f"{w['config']}.json"),
                   traffic=traffic.load(w["traffic"]),
                   end_to_end=[m for m in bench["end_to_end"] if here(m)],
                   per_layer=[m for m in bench["per_layer"] if here(m)],
                   limits=load_json(HERE, "limits", f"{workload}.json"))


def program_cfg(config: Dict[str, Any]):
    """The program's ``ModelCfg`` for ``config["arch"]`` with every key of
    ``config["model"]`` that names one of its fields set from the file."""
    from repro.configs import ARCHS
    from repro.models.config import ModelCfg, SSMCfg
    cfg = ARCHS[config["arch"]].cfg
    fields = {f.name for f in dataclasses.fields(ModelCfg)}
    over = {}
    for k, v in config["model"].items():
        if k not in fields:
            continue
        if k == "ssm":
            v = SSMCfg(**v)
        elif k == "pattern":
            v = tuple(v)
        over[k] = v
    return cfg.replace(**over)


# ----------------------------------------------------------- what is recorded
class Recorder:
    """Host timestamps of the engine's calls (``time.monotonic``, the
    clock of the program's records), each inside a
    ``TraceAnnotation("bench.<call>#<index>")``."""

    def __init__(self):
        self.prefill: List[tuple] = []   # (t0, t1, prompt_len)
        self.attach: List[tuple] = []    # (t0, t1, slot)
        self.step: List[tuple] = []      # (t0, t1, live slots, ctx lens)
        self._pos: Dict[int, int] = {}

    def wrap(self, eng) -> None:
        import jax
        ann = jax.profiler.TraceAnnotation
        prefill, attach, step = eng.prefill, eng.attach, eng.step

        def w_prefill(prompt):
            i = len(self.prefill)
            with ann(f"bench.prefill#{i}"):
                t0 = time.monotonic()
                out = prefill(prompt)
                self.prefill.append((t0, time.monotonic(), len(prompt)))
            return out

        def w_attach(slot, prompt_len, first_token, pcache):
            i = len(self.attach)
            with ann(f"bench.attach#{i}"):
                t0 = time.monotonic()
                attach(slot, prompt_len, first_token, pcache)
                self.attach.append((t0, time.monotonic(), slot))
            self._pos[slot] = prompt_len

        def w_step(live):
            i = len(self.step)
            live = list(live)
            # the token fed at position p attends to keys 0..p
            ctx = [self._pos.get(s, 0) + 1 for s in live]
            with ann(f"bench.step#{i}"):
                t0 = time.monotonic()
                out = step(live)
                self.step.append((t0, time.monotonic(), live, ctx))
            for s in live:
                self._pos[s] = self._pos.get(s, 0) + 1
            return out

        eng.prefill, eng.attach, eng.step = w_prefill, w_attach, w_step


class CompileLog:
    """Counts the executables JAX compiles or loads from its persistent
    cache, with the time of each (one listener per process)."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **_kw):
        if name in self.NAMES:
            self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)


# ------------------------------------------------------------- the program
def _program_class():
    from repro import edat
    from repro.serve import LoadSpec, ServeProgram
    from repro.serve.program import BACKPRESSURE, READY, REQUEST, RESPONSE

    class BenchProgram(ServeProgram):
        """``ServeProgram`` with a given engine and the benchmark's load
        ranks: they replay ``plan`` from a window start they share."""

        def __init__(self, cfg, engine, plan: "Plan", *, slots: int,
                     max_len: int, queue_bound: int):
            # the engine is warm: the server's own warm-up has no bucket
            # left to compile
            super().__init__(cfg, slots=slots, max_len=max_len,
                             load=LoadSpec(prompt_lens=()),
                             queue_bound=queue_bound)
            self._engine = engine
            self.plan = plan

        def _run_client(self, ctx):
            plan = self.plan
            resume = threading.Event()
            resume.set()

            def on_backpressure(c, events):
                if events[0].data["on"]:
                    resume.clear()
                else:
                    resume.set()

            ctx.submit_persistent(on_backpressure, deps=[(0, BACKPRESSURE)],
                                  name=f"client{ctx.rank}.bp")
            ctx.submit_persistent(lambda c, e: None, deps=[(0, RESPONSE)],
                                  name=f"client{ctx.rank}.resp")
            ctx.wait([(0, READY)])
            t0 = plan.start()
            end = t0 + plan.seconds
            last_t, last_fire = 0.0, t0
            for req in plan.clients[ctx.rank - 1]:
                if plan.closes:
                    # paced from the last send: a client that was held
                    # back resumes at its rate instead of flooding the
                    # queue with everything that fell due meanwhile
                    target = last_fire + req["t"] - last_t
                else:
                    target = t0 + req["t"]
                delay = target - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                throttled = 0.0
                if not resume.is_set():
                    tw = time.monotonic()
                    resume.wait(self.throttle_timeout)
                    throttled = time.monotonic() - tw
                if plan.closes and time.monotonic() >= end:
                    break
                plan.sent(req["id"])
                last_t, last_fire = req["t"], time.monotonic()
                ctx.fire(0, REQUEST,
                         {"id": req["id"], "prompt": req["prompt"],
                          "max_new": req["max_new"], "t_sched": target,
                          "t_send": time.monotonic(),
                          "throttled_s": throttled})

    return BenchProgram, edat


class Plan:
    """The window's schedule, shared by the load ranks of one process."""

    def __init__(self, reqs: List[Dict[str, Any]], clients: int,
                 seconds: float, closes: bool):
        self.reqs = {r["id"]: r for r in reqs}
        self.clients = traffic.by_client(reqs, clients)
        self.seconds = seconds
        self.closes = closes
        self.t0: Optional[float] = None
        self.started = threading.Event()
        self.fired: List[int] = []
        self._mu = threading.Lock()

    def start(self) -> float:
        with self._mu:
            if self.t0 is None:
                self.t0 = time.monotonic() + 0.005
                self.started.set()
            return self.t0

    def sent(self, rid: int) -> None:
        with self._mu:
            self.fired.append(rid)


# ------------------------------------------------------------------ a window
@dataclasses.dataclass
class Window:
    """What one measured window left behind."""

    seed: int
    seconds: float
    t0: float
    plan: Plan
    result: Dict[str, Any]
    calls: Recorder
    compiles: int
    host: Dict[str, Any]   # ``stalls.StallWatch.summary`` of the window

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds

    @property
    def records(self) -> List[Dict[str, Any]]:
        return self.result["records"]


class Bench:
    """The program for one cell, warm, on the chip: build it once, then
    serve as many windows as needed, each with the weights of a seed."""

    def __init__(self, cell: Cell):
        self.cell = cell
        self.cfg = program_cfg(cell.config)
        self.compiles = CompileLog()
        self.engine = None

    # -- weights and the engine ------------------------------------------
    def build(self, seed: int) -> None:
        """Weights of ``seed``, the engine on them, and every shape this
        cell's traffic uses compiled: each prompt bucket's prefill, the
        decode step, and the splice of a prefilled cache into a slot."""
        from repro.serve import ServeEngine
        from repro.serve import engine as engine_mod
        params = self._params(seed)
        real = engine_mod.build_model

        def build_model(cfg):
            model = real(cfg)
            model.init = lambda key: params
            return model

        engine_mod.build_model = build_model
        try:
            eng = ServeEngine(self.cfg, slots=self.cell.serving["slots"],
                              max_len=self.cell.serving["max_len"])
        finally:
            engine_mod.build_model = real
        if eng.params is not params:
            raise RuntimeError("the engine did not take the benchmark's "
                               "weights")
        self.engine = eng
        self._warm()

    def set_weights(self, seed: int) -> None:
        """Serve the weights of another seed on the same warm engine."""
        self.engine.params = None
        gc.collect()
        self.engine.params = self._params(seed)
        self._warm()

    def _params(self, seed: int):
        from repro.models import build_model
        model = build_model(self.cfg)
        return weights.to_program(self.cell.model,
                                  weights.canonical(self.cell.model, seed),
                                  model)

    def _warm(self) -> None:
        eng = self.engine
        lens = self.cell.traffic["prompt_lens"]
        first, pcache = eng.prefill([0] * lens[0])
        eng.attach(0, lens[0], first, pcache)
        del pcache
        eng.warmup(lens)

    def release(self) -> None:
        """Free the program's weights and cache."""
        self.engine = None
        gc.collect()

    # -- one window --------------------------------------------------------
    def window(self, seed: int, seconds: float,
               trace_dir: Optional[str] = None) -> Window:
        BenchProgram, edat = _program_class()
        cell, eng = self.cell, self.engine
        mix = cell.traffic
        reqs = traffic.schedule(mix, seconds, seed, cell.model["vocab"])
        plan = Plan(reqs, int(mix["clients"]), seconds,
                    closes=mix["arrival"] == "saturate")
        calls = Recorder()
        eng.warmup(())
        calls.wrap(eng)
        prog = BenchProgram(self.cfg, eng, plan,
                            slots=cell.serving["slots"],
                            max_len=cell.serving["max_len"],
                            queue_bound=cell.serving["queue_bound"])
        tracer = None
        if trace_dir is not None:
            tracer = threading.Thread(target=_trace_end_of_window,
                                      args=(plan, trace_dir), daemon=True)
            tracer.start()
        watch = stalls.StallWatch()
        watch.start()
        try:
            with edat.Session(1 + int(mix["clients"]), workers_per_rank=2,
                              unconsumed="ignore",
                              timeout=seconds + DRAIN_TIMEOUT) as s:
                s.run(prog)
        finally:
            watch.stop()
            for name in ("prefill", "attach", "step"):
                eng.__dict__.pop(name, None)
        if tracer is not None:
            tracer.join()
        t0 = plan.t0
        return Window(seed=seed, seconds=seconds, t0=t0, plan=plan,
                      result=prog.result(), calls=calls,
                      compiles=self.compiles.between(t0, t0 + seconds),
                      host=watch.summary(t0, t0 + seconds))


def _trace_end_of_window(plan: Plan, trace_dir: str) -> None:
    import jax
    if not plan.started.wait(DRAIN_TIMEOUT):
        return
    start = plan.t0 + max(0.0, plan.seconds - TRACE_SECONDS)
    time.sleep(max(0.0, start - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(max(0.0, plan.t0 + plan.seconds - time.monotonic()))
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------- correctness
def served_rows(cell: Cell, win: Window, seed: int) -> List[Dict[str, Any]]:
    """The requests whose tokens are compared: drawn from the seed among
    those served, with the longest (prompt plus output) always in."""
    recs = sorted(win.records, key=lambda r: r["id"])
    if not recs:
        return []
    k = min(int(cell.traffic["check_requests"]), len(recs))
    longest = max(range(len(recs)),
                  key=lambda i: (recs[i]["prompt_len"] + recs[i]["n_out"],
                                 -recs[i]["id"]))
    rest = [i for i in range(len(recs)) if i != longest]
    pick = traffic.rng_for(seed, 2).choice(len(rest), size=k - 1,
                                           replace=False)
    rows = []
    for i in [longest] + [rest[j] for j in sorted(pick)]:
        r = recs[i]
        rows.append({"id": r["id"], "prompt": win.plan.reqs[r["id"]]["prompt"],
                     "tokens": list(r["tokens"])})
    return rows


def row_arrays(cell: Cell, rows: List[Dict[str, Any]]):
    """``tokens`` and ``targets`` ``(rows, max_len)``: the prompt and the
    served tokens fed back, and at each position whose next token was
    served, that token (else -1)."""
    L = cell.serving["max_len"]
    toks = np.zeros((len(rows), L), np.int32)
    tgts = np.full((len(rows), L), -1, np.int32)
    for i, r in enumerate(rows):
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        p = len(r["prompt"])
        toks[i, :len(seq)] = seq
        tgts[i, p - 1:p - 1 + len(r["tokens"])] = r["tokens"]
    return toks, tgts


def logit_gaps(cell: Cell, seed: int, rows: List[Dict[str, Any]],
               control: bool = False) -> np.ndarray:
    """Per served token, how far below the reference's best logit it lies
    (``control``: the token the fp8 reference puts first), over ``rows``
    in blocks of ``reference_rows``.  The weights are made again from the
    seed; nothing the program made is used."""
    w = weights.canonical(cell.model, seed)
    served, ctrl = _gap_fns(json.dumps(cell.model, sort_keys=True))
    fn = ctrl if control else served
    R = int(cell.serving["reference_rows"])
    toks, tgts = row_arrays(cell, rows)
    out = []
    for b in range(0, len(rows), R):
        t, g = toks[b:b + R], tgts[b:b + R]
        pad = R - len(t)
        if pad:
            t = np.concatenate([t, np.zeros((pad,) + t.shape[1:], t.dtype)])
            g = np.concatenate([g, np.full((pad,) + g.shape[1:], -1, g.dtype)])
        gap = np.asarray(fn(w, t, g))
        out.append(gap[g >= 0])
    return np.concatenate(out) if out else np.zeros(0)


@functools.lru_cache(maxsize=None)
def _gap_fns(model_json: str):
    return reference.make_gap_fns(json.loads(model_json))


def served_ok(cell: Cell, win: Window) -> Dict[str, int]:
    """Attempted requests (fired by the load ranks), and those that came
    back wrong: no record, another count of tokens than asked for, or a
    token outside the vocabulary."""
    by_id = {r["id"]: r for r in win.records}
    vocab = cell.model["vocab"]
    failed = 0
    for rid in win.plan.fired:
        r, want = by_id.get(rid), win.plan.reqs[rid]["max_new"]
        if (r is None or r["n_out"] != want
                or any(not 0 <= t < vocab for t in r["tokens"])):
            failed += 1
    return {"attempted": len(win.plan.fired), "failed": failed}


def compare(cell: Cell, win: Window, seed: int, control: bool = False):
    """The numbers that decide ``correct``, each with its limit, and
    whether all of them hold: ``(correct, compared, rows, gaps)``.
    ``control`` puts, at each compared position, the token the fp8
    reference puts first in place of the served one: the control, which
    has to come out not correct."""
    ok = served_ok(cell, win)
    leaked = win.result["slots_leaked"]
    rows = served_rows(cell, win, seed)
    gaps = logit_gaps(cell, seed, rows, control=control)
    gap = float(gaps.max()) if gaps.size else float("inf")
    limit = float(cell.limits["max_logit_gap"]["limit"])
    compared = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": ok["failed"], "limit": 0},
        "slots_leaked": {"value": leaked, "limit": 0},
    }
    correct = bool(rows) and gap <= limit and ok["failed"] == 0 \
        and leaked == 0
    return correct, compared, rows, gaps


# --------------------------------------------------------------- metrics
class RunData:
    """What a metric reader may read: the window, its records and calls,
    the trace summary (traced runs only), the counts and the chip's peak."""

    def __init__(self, cell: Cell, win: Window, setup_s: float,
                 peak: Dict[str, float],
                 trace: Optional[Dict[str, Any]] = None):
        self.cell = cell
        self.win = win
        self.setup_s = setup_s
        self.peak = peak
        self.trace = trace

    @property
    def records(self) -> List[Dict[str, Any]]:
        """Requests scheduled in the window, served."""
        return [r for r in self.win.records
                if r["t_sched"] < self.win.t1]

    def in_window(self, calls) -> list:
        return [c for c in calls
                if self.win.t0 <= c[0] and c[1] <= self.win.t1]

    def counts(self, phase: str, arg) -> tuple:
        """``(flops, bytes)`` of a whole prefill (``arg`` = prompt length)
        or decode step (``arg`` = live slots' context lengths)."""
        m = self.cell.model
        common = importlib.import_module(f"{__package__}.counts.common")
        n_layers = m["n_layers"] // len(m["pattern"])
        flops = nbytes = 0
        for kind in m["pattern"]:
            mod = importlib.import_module(f"{__package__}.counts.{kind}")
            f, b = getattr(mod, phase)(m, arg)
            flops += n_layers * f
            nbytes += n_layers * b
        if phase == "prefill":
            f, b = common.outside(m, arg, 1)
        else:
            f, b = common.outside(m, len(arg), len(arg))
        return flops + f, nbytes + b

    def least_s(self, phase: str, arg) -> float:
        f, b = self.counts(phase, arg)
        return max(f / self.peak["flops_bf16"],
                   b / self.peak["hbm_bytes_per_s"])

    def tick_intervals(self):
        """Consecutive decode steps in the window between which a slot
        stayed live: ``(previous call, next call)`` pairs."""
        steps = self.in_window(self.win.calls.step)
        return [(a, b) for a, b in zip(steps, steps[1:])
                if set(a[2]) & set(b[2])]


def metric_value(name: str, run: RunData) -> Optional[float]:
    """The reader ``metrics/<base>.py`` of a metric ``<base>[.<split>]``."""
    base = name.split(".", 1)[0]
    mod = importlib.import_module(f"{__package__}.metrics.{base}")
    return mod.read(run)


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(HERE, "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def trace_window(bench: Bench, seed: int, seconds: float):
    """A window with its last ``TRACE_SECONDS`` traced, and the trace's
    reduction."""
    from . import trace
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as d:
        win = bench.window(seed, seconds, trace_dir=d)
        ev = trace.extract(d)
    return win, trace.reduce(ev)
