"""The trace reduction: busy and idle time, time per device program, idle
gaps by host span, and executions matched to the host spans that issued
them — on a hand-made trace, and on a trace recorded on a TPU v5e."""
from __future__ import annotations

import gzip
import json
import os

import numpy as np
import pytest

import _chipbench  # noqa: F401
from chip import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"


def _hand_made():
    # union 10-30, 50-60, 90-120
    ops = [(10, 20), (15, 30), (50, 60), (90, 120)]
    dev = [[DEV, trace.OPS, f"op{i}", s, e - s] for i, (s, e) in
           enumerate(ops)]
    dev += [[DEV, trace.MODULES, "jit(serve_step)", 10, 20],
            [DEV, trace.MODULES, "jit(prefill_step)", 50, 10],
            [DEV, trace.MODULES, "jit(serve_step)", 90, 30]]
    host = [["bench.window", 0, 100],
            ["bench.step#7", 5, 30], ["bench.prefill#3", 40, 25],
            ["bench.step#8", 85, 40], ["bench.attach#1", 62, 10]]
    return {"device": dev, "host": host}


def test_hand_made_trace():
    r = trace.reduce(_hand_made())
    assert r["window_ns"] == 100
    assert r["busy_ns"] == 20 + 10 + 10          # clipped to [0, 100)
    assert r["modules"]["jit(serve_step)"] == [20 + 10, 2]
    assert r["modules"]["jit(prefill_step)"] == [10, 1]
    # gaps 60-90, 30-50 and 0-10, longest first, each labelled by the
    # latest-opened span at its start (the window span does not count)
    assert r["idle_gaps"] == [("bench.prefill", 30), ("bench.step", 20),
                              ("host.other", 10)]
    assert r["matched"]["bench.step"] == [[7, 20, "jit(serve_step)"]]
    assert r["matched"]["bench.prefill"] == [[3, 10, "jit(prefill_step)"]]


def test_no_device_plane_is_an_error():
    ev = _hand_made()
    ev["device"] = []
    with pytest.raises(ValueError, match="no device"):
        trace.reduce(ev)


def _busy_by_grid(ev, w0, w1, dev):
    """Busy time by marking every nanosecond an operation covers."""
    grid = np.zeros(int(w1 - w0), bool)
    for d, line, _, s, dur in ev["device"]:
        if d == dev and line == trace.OPS:
            a, b = max(s, w0) - w0, min(s + dur, w1) - w0
            if b > a:
                grid[int(a):int(b)] = True
    return int(grid.sum())


def test_recorded_chip_trace():
    with gzip.open(os.path.join(DATA, "trace_v5e_chat.json.gz"), "rt") as f:
        ev = json.load(f)
    r = trace.reduce(ev)
    w0 = [s for n, s, d in ev["host"] if n == trace.WINDOW_SPAN][0]
    assert r["busy_ns"] == pytest.approx(
        _busy_by_grid(ev, w0, w0 + r["window_ns"], r["device"]), abs=2)
    assert 0 < r["busy_ns"] < r["window_ns"]
    names = " ".join(r["modules"])
    assert "serve_step" in names
    steps = [m for m in r["matched"].get("bench.step", [])
             if "serve_step" in m[2]]
    assert steps and all(d > 0 for _, d, _ in steps)
    total = sum(t for t, _ in r["modules"].values())
    assert total <= r["window_ns"] * 1.001

