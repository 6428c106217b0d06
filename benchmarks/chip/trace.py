"""From the profiler's trace to device busy and idle time, time per device
program, and the longest idle gaps by what the host was doing.

``extract`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists: the device planes' ``XLA Modules`` (one event per
program execution) and ``XLA Ops`` (one per operation) lines, and the
host's ``bench.*`` spans (``jax.profiler.TraceAnnotation`` around the
harness's calls).  ``reduce`` works on those lists alone, so it can be
checked on a trace recorded once and kept as data.
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Tuple

MODULES, OPS = "XLA Modules", "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: longest idle gaps and busiest programs kept for the breakdown
TOP = 10


def extract(trace_dir: str) -> Dict[str, Any]:
    """``{"device": [[device, line, name, start_ns, dur_ns], ...],
    "host": [[name, start_ns, dur_ns], ...]}`` from the newest trace
    under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device: List[list] = []
    host: List[list] = []
    for plane in data.planes:
        on_device = (plane.name.startswith("/device:")
                     and not plane.name.startswith("/device:CPU"))
        for line in plane.lines:
            if on_device and line.name in (MODULES, OPS):
                device.extend([plane.name, line.name, e.name, e.start_ns,
                               e.duration_ns] for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"device": device, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def span_label(name: str) -> str:
    """``bench.step#12`` -> ``bench.step``."""
    return name.split("#", 1)[0]


def reduce(ev: Dict[str, Any],
           window: Optional[Tuple[float, float]] = None) -> Dict[str, Any]:
    """Busy and idle time of the first device, its time per program, the
    ``TOP`` longest idle gaps labelled by the innermost host span open at
    their start, and each program execution matched to the host span
    that issued it.

    ``window`` (ns) defaults to the ``bench.window`` span.  Busy time is
    the union of the operations' intervals clipped to the window;
    ``matched[label]`` lists ``[index, device_ns]`` for each execution
    whose midpoint lies inside the host span ``label#index``."""
    host = [(n, s, s + d) for n, s, d in ev["host"]]
    if window is None:
        spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN} span")
        window = spans[0]
    w0, w1 = window
    devices = sorted({d for d, *_ in ev["device"]})
    if not devices:
        raise ValueError("trace has no device plane: nothing ran on a chip")
    dev = devices[0]

    def clip(s, e):
        return max(s, w0), min(e, w1)

    ops = [clip(s, s + d) for dv, line, n, s, d in ev["device"]
           if dv == dev and line == OPS and s < w1 and s + d > w0]
    busy = _union(ops)
    busy_ns = sum(e - s for s, e in busy)
    gaps, prev = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)

    def label_at(t):
        inside = [(s, n) for n, s, e in host
                  if s <= t < e and n != WINDOW_SPAN]
        return span_label(max(inside)[1]) if inside else "host.other"

    modules: Dict[str, List[float]] = {}
    execs = []
    for dv, line, n, s, d in ev["device"]:
        if dv != dev or line != MODULES or not (s < w1 and s + d > w0):
            continue
        cs, ce = clip(s, s + d)
        tot = modules.setdefault(n, [0.0, 0])
        tot[0] += ce - cs
        tot[1] += 1
        execs.append((n, s, d))
    matched: Dict[str, List[List[float]]] = {}
    for n, s, d in execs:
        mid = s + d / 2
        for hn, hs, he in host:
            if "#" in hn and hs <= mid < he and s >= w0 and s + d <= w1:
                label, idx = hn.split("#", 1)
                matched.setdefault(label, []).append([int(idx), d, n])
    return {
        "device": dev,
        "window_ns": w1 - w0,
        "busy_ns": busy_ns,
        "modules": modules,
        "idle_gaps": [(label_at(s), e - s) for s, e in
                      sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]],
        "matched": matched,
    }

