"""The readers of the program's spans, on hand-made spans with known
answers; the spans put on the profiler's clock, with the device's idle
time by span, on hand-made device events; and every reader on a tiny
cell's window served on the CPU with spans on."""
from __future__ import annotations

import types

import pytest

import _chipbench
from chip import harness, spans, trace

M = 1_000_000          # ns in a ms
READERS = ["task_start_wait_p95_ms", "admit_lock_wait_p95_ms",
           "tick_lock_wait_ms", "step_host_ms", "itl_p99_ms"]


def _task(sid, name, ready, t0, t1):
    return ("edat.task", t0, t1, sid, 0, {"task": name, "ready_ns": ready})


def _hand_made_run():
    """Request 1 waits 3 + 1 ms for workers and 2 + 4 ms for the lock;
    request 2 waits 0 + 4 ms and never for the lock.  Two decode ticks
    step the batch (lock waits 1 and 0 ms; host time outside the read
    1.5 and 2 ms), a third finds it empty."""
    S = [
        _task(10, "serve.request", 2 * M, 5 * M, 8 * M),
        ("serve.request", 5 * M, 8 * M, 11, 10, {"req": 1}),
        ("edat.lock_wait", 5 * M, 7 * M, 12, 11, {"lock": "server"}),
        _task(20, "serve.prefill", 9 * M, 10 * M, 30 * M),
        ("serve.prefill", 10 * M, 20 * M, 21, 20, {"req": 1}),
        ("serve.attach", 20 * M, 25 * M, 22, 20, {"req": 1}),
        ("edat.lock_wait", 20 * M, 24 * M, 23, 22, {"lock": "server"}),
        _task(30, "serve.request", 40 * M, 40 * M, 41 * M),
        ("serve.request", 40 * M, 41 * M, 31, 30, {"req": 2}),
        _task(40, "serve.prefill", 41 * M, 45 * M, 62 * M),
        ("serve.prefill", 45 * M, 60 * M, 41, 40, {"req": 2}),
        ("serve.attach", 60 * M, 61 * M, 42, 40, {"req": 2}),
        _task(50, "serve.decode", 26 * M, 26 * M, 40 * M),
        ("edat.lock_wait", 26 * M, 27 * M, 51, 50, {"lock": "server"}),
        ("serve.step", 27 * M, 39 * M, 52, 50, {"live": (1,)}),
        ("engine.step", 27 * M, 39 * M, 53, 52, {}),
        ("engine.step.launch", 27 * M, 28 * M, 54, 53, {}),
        ("engine.step.read", 28 * M, 38_500_000, 55, 53, {}),
        _task(60, "serve.decode", 62 * M, 62 * M, 80 * M),
        ("serve.step", 62 * M, 79 * M, 61, 60, {"live": (1, 2)}),
        ("engine.step", 62 * M, 79 * M, 62, 61, {}),
        ("engine.step.read", 63 * M, 78 * M, 63, 62, {}),
        _task(70, "serve.decode", 81 * M, 81 * M, 82 * M),
        ("edat.lock_wait", 81 * M, 81_500_000, 71, 70, {"lock": "server"}),
        # a request scheduled after the window, and a span past its end
        ("serve.request", 95 * M, 96 * M, 81, 80, {"req": 99}),
        ("engine.step", 990 * M, 1010 * M, 91, 90, {}),
    ]
    recs = [{"id": 1}, {"id": 2}, {"id": 3}]      # 3 has no spans
    win = types.SimpleNamespace(t0=0.0, t1=1.0)
    return types.SimpleNamespace(spans=S, win=win, records=recs)


@pytest.mark.parametrize("name,want", [
    ("task_start_wait_p95_ms", 4.0),     # both requests 4 ms
    ("admit_lock_wait_p95_ms", 6.0),     # nearest rank of [0, 6]
    ("tick_lock_wait_ms", 0.5),          # the empty tick does not count
    ("step_host_ms", 1.75),              # (1.5 + 2) / 2
    ("itl_p99_ms", 40.0),                # gaps 14, 40 (request 1), 18 (2)
])
def test_reader_on_hand_made_spans(name, want):
    from importlib import import_module
    read = import_module(f"chip.metrics.{name}").read
    assert read(_hand_made_run()) == pytest.approx(want)
    no_spans = types.SimpleNamespace(win=_hand_made_run().win, records=[])
    assert read(no_spans) is None        # a harness that keeps no spans
    # the trace of a program from before spans: tuples of other shapes
    old = types.SimpleNamespace(win=no_spans.win, records=[{"id": 1}],
                                spans=[("recv", 5.0, 1, "request"),
                                       ("task", 5.0, 0.1, "serve.step", 1)])
    assert read(old) is None


OFF = 10 ** 12         # the profiler's clock minus monotonic_ns


def _device_and_spans():
    """Device busy 2-20, 30-60, 68-85, 90-100 ms of a 100 ms window, so
    idle 0-2, 20-30, 60-68 and 85-90 ms; in those the decode thread
    reads tokens, a request waits for the lock (a client task opened
    later, but shallower), the decode task runs outside any step, and
    nothing is open."""
    dev = "/device:TPU:0"
    ops = [(2, 20), (30, 60), (68, 85), (90, 100)]
    ev = {"device": [[dev, trace.OPS, "op", OFF + s * M, (e - s) * M]
                     for s, e in ops],
          "host": [["bench.window", OFF, 100 * M],
                   ["bench.step#0", OFF + 10 * M - 2000, 16 * M],
                   ["bench.step#1", OFF + 50 * M - 3000, 5 * M],
                   ["bench.step#2", OFF + 90 * M - 1000, 5 * M]]}
    steps = [(0.010, 0.026, [0], [1]), (0.050, 0.055, [0], [2]),
             (0.090, 0.095, [0], [3])]
    S = [_task(1, "serve.decode", 10 * M, 10 * M, 27 * M),
         ("serve.step", 11 * M, 26 * M, 2, 1, {"live": (1,)}),
         ("engine.step", 12 * M, 26 * M, 3, 2, {}),
         ("engine.step.read", 15 * M, 25 * M, 4, 3, {}),
         _task(5, "serve.request", 56 * M, 56 * M, 66 * M),
         ("serve.request", 57 * M, 66 * M, 6, 5, {"req": 7}),
         ("edat.lock_wait", 58 * M, 65 * M, 7, 6, {"lock": "server"}),
         _task(8, "client1.resp", 59 * M, 59 * M, 61 * M),
         _task(9, "serve.decode", 80 * M, 80 * M, 95 * M)]
    return ev, S, steps


def test_spans_on_the_profilers_clock():
    ev, S, steps = _device_and_spans()
    off, err = spans.align(ev["host"], steps)
    assert off == pytest.approx(OFF - 2000, abs=1)    # the median
    assert err == pytest.approx(1000, abs=1)
    idle = spans.idle_by_span(ev, S, off)
    assert idle == [("engine.step.read", 10 * M), ("edat.lock_wait", 8 * M),
                    ("serve.decode", 5 * M), ("host.other", 2 * M)]
    red = trace.reduce(ev)
    assert sum(t for _, t in idle) == red["window_ns"] - red["busy_ns"]
    out = spans.breakdown(ev, S, steps)
    assert out["idle_s"] == pytest.approx(0.025)
    assert out["idle_by_span"][0] == ["engine.step.read", pytest.approx(0.01)]
    assert spans.breakdown(ev, [], steps) is None
    assert spans.breakdown(ev, [("task", 5.0, 0.1, "x", 1)], steps) is None


def test_every_reader_reads_a_tiny_window_with_spans_on(monkeypatch):
    """A tiny cell served on the CPU through the harness, its session
    traced: every reader finds what it reads, and no span was dropped."""
    from repro import edat
    kept = {}

    class Traced(edat.Session):
        def __init__(self, *a, **kw):
            kw["trace"] = True
            super().__init__(*a, **kw)

        def run(self, *a, **kw):
            out = super().run(*a, **kw)
            kept.update(self.stats()["ranks"])
            return out

    monkeypatch.setattr(edat, "Session", Traced)
    cell = _chipbench.tiny_cell("attn")
    bench = harness.Bench(cell)
    bench.build(4294967311)
    win = bench.window(4294967311, 1.5)
    bench.release()
    assert sum(rk["trace_dropped"] for rk in kept.values()) == 0

    class Run(harness.RunData):
        spans = [r for rk in kept.values() for r in rk["trace"]]

    run = Run(cell, win, 0.0, {})
    for name in READERS:
        assert harness.metric_value(name, run) is not None, name
