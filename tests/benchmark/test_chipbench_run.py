"""``run.py`` end to end at a small size on the CPU, past its look for a
chip: a sound run is correct, and each fault the serving path can have,
planted in the program underneath the timed path, makes ``correct``
false.  Without a TPU, or without the program beside it, it exits
non-zero and prints no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import _chipbench
from chip import harness
from chip import run as run_mod

ARGV = ["--workload", "tiny", "--seed", "4294967311", "--seconds", "1.5",
        "--trace", "0"]
E2E = [{"name": n, "unit": u} for n, u in
       (("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"), ("setup_s", "s"))]


def _run(kind="attn"):
    return run_mod.main(ARGV, cell=_chipbench.tiny_cell(kind, end_to_end=E2E),
                        require_tpu=False)


def _token_altered(engine_cls, monkeypatch):
    orig = engine_cls.step

    def step(self, live):
        out = np.array(orig(self, live))
        out[list(live)] = (out[list(live)] + 1) % self.cfg.vocab
        return out
    monkeypatch.setattr(engine_cls, "step", step)


def _state_unchanged(engine_cls, monkeypatch):
    orig = engine_cls.step

    def step(self, live):
        before = self.caches
        out = orig(self, live)
        self.caches = before
        return out
    monkeypatch.setattr(engine_cls, "step", step)


def _splice_skipped(engine_cls, monkeypatch):
    orig = engine_cls.attach

    def attach(self, slot, prompt_len, first_token, pcache):
        before = self.caches
        orig(self, slot, prompt_len, first_token, pcache)
        self.caches = before
    monkeypatch.setattr(engine_cls, "attach", attach)


def _first_token_altered(engine_cls, monkeypatch):
    orig = engine_cls.prefill

    def prefill(self, prompt):
        tok, pcache = orig(self, prompt)
        return (tok + 1) % self.cfg.vocab, pcache
    monkeypatch.setattr(engine_cls, "prefill", prefill)


@pytest.mark.parametrize("kind", ["attn", "ssd"])
def test_sound_run_is_correct(kind):
    out = _run(kind)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert out["compared"]["max_logit_gap"]["value"] <= 1e-3
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _splice_skipped, _first_token_altered])
def test_fault_makes_run_incorrect(fault, monkeypatch):
    from repro.serve.engine import ServeEngine
    fault(ServeEngine, monkeypatch)
    out = _run()
    assert not out["correct"]
    assert out["compared"]["max_logit_gap"]["value"] > 1e-3


@pytest.mark.parametrize("kind", ["attn", "ssd"])
def test_fp8_control_fails_the_limit(kind):
    """The control (at each compared position, the token the fp8
    reference puts first in place of the served one), judged by the run's
    own comparison, comes out not correct at the limit a sound run of the
    same seed passes (``test_sound_run_is_correct``)."""
    out = run_mod.main(ARGV, cell=_chipbench.tiny_cell(kind, end_to_end=E2E),
                       require_tpu=False, control=True)
    assert not out["correct"]
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert out["failed"] == 0 and out["compared"]["slots_leaked"]["value"] == 0


def test_fp8_control_reads_far_above_the_bf16_program():
    cell = _chipbench.tiny_cell("attn", "bfloat16")
    bench = harness.Bench(cell)
    bench.build(21)
    win = bench.window(21, 1.5)
    bench.release()
    _, prog, _, _ = harness.compare(cell, win, 21)
    _, ctrl, _, _ = harness.compare(cell, win, 21, control=True)
    assert ctrl["max_logit_gap"]["value"] > 3 * prog["max_logit_gap"]["value"]


def _argv():
    return ["--workload", "stablelm-1.6b.chat", "--seed", "1",
            "--seconds", "1", "--trace", "0"]


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", *_argv()],
                       cwd=_chipbench.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no chip" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    root = _chipbench.ROOT
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    for d in ("benchmarks/chip", "tests/benchmark"):
        shutil.copytree(os.path.join(root, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", *_argv()],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_saturating_clients_are_held_back_and_stop():
    """Offered far above what the server sustains, the load ranks are
    held by the program's backpressure, send fewer requests than were
    due, and stop at the window's end: the queue is short when it closes."""
    cell = _chipbench.tiny_cell("attn")
    cell.traffic.update(arrival="saturate", rate_rps=400.0, max_new=[20, 40])
    cell.serving["queue_bound"] = 4
    bench = harness.Bench(cell)
    bench.build(3)
    win = bench.window(3, 1.5)
    assert win.result["bp_signals"] > 0
    assert 0 < len(win.plan.fired) < len(win.plan.reqs) / 2
    assert max(r["t_sched"] for r in win.records) < win.t1
    assert harness.served_ok(cell, win)["failed"] == 0
