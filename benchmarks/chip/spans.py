"""The program's own spans, for the readers of ``metrics/`` and on the
profiler's clock.

A traced session (``edat.Session(trace=True)``) records, per rank, spans
``(name, t0_ns, t1_ns, span_id, parent_id, attrs)`` on the host's
``time.monotonic_ns()`` (``repro.core.trace``), the clock of the
program's ``t_*`` records and of ``harness.Recorder``: ``edat.task``
(attrs ``task``, ``ready_ns``) and ``edat.lock_wait`` (``lock``) from the
runtime; ``serve.request``, ``serve.prefill``, ``serve.attach`` (``req``)
and ``serve.step`` (``live``) from the program; ``engine.step`` with its
``engine.step.launch`` and ``engine.step.read`` from the engine.

A reader finds them as ``run.spans``, every rank's spans of the window's
session in one list.  Where a run has none (an untraced window, or a
program that records no spans) :func:`of` gives ``None``, and so does the
reader.  :func:`breakdown` puts the spans on the profiler's clock and
sums the device's idle time by the span open at each gap.
"""
from __future__ import annotations

import statistics
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from .trace import OPS, TOP, WINDOW_SPAN, _union

STEP_SPAN = "bench.step"


def dur(rec: tuple) -> int:
    return rec[2] - rec[1]


class Spans:
    """One window's spans, indexed by id, parent and name; ``w0``/``w1``
    are the window's bounds in ns."""

    def __init__(self, records: Sequence[tuple], t0_s: float, t1_s: float):
        self.records = list(records)
        self.w0, self.w1 = t0_s * 1e9, t1_s * 1e9
        self.by_id = {r[3]: r for r in self.records}
        self.kids: Dict[int, List[tuple]] = {}
        for r in self.records:
            self.kids.setdefault(r[4], []).append(r)

    def named(self, name: str) -> List[tuple]:
        return [r for r in self.records if r[0] == name]

    def in_window(self, name: str) -> List[tuple]:
        return [r for r in self.named(name)
                if self.w0 <= r[1] and r[2] <= self.w1]

    def children(self, rec: tuple, name: str) -> List[tuple]:
        return [k for k in self.kids.get(rec[3], ()) if k[0] == name]

    def start_wait(self, rec: tuple) -> int:
        """How long the task that holds ``rec`` waited in the ready queue
        for a worker."""
        task = self.by_id[rec[4]]
        return task[1] - task[5]["ready_ns"]

    def lock_wait(self, rec: tuple, lock: str = "server") -> int:
        """Time ``rec``'s own acquisitions of ``lock`` waited."""
        return sum(dur(k) for k in self.children(rec, "edat.lock_wait")
                   if k[5]["lock"] == lock)

    def per_request(self, records: Sequence[Dict[str, Any]],
                    *names: str) -> Iterator[Tuple[tuple, ...]]:
        """For each request in ``records`` that has a span of each of
        ``names``, those spans."""
        index = [{r[5]["req"]: r for r in self.named(n)} for n in names]
        for rec in records:
            got = tuple(ix.get(rec["id"]) for ix in index)
            if None not in got:
                yield got


def shaped(records: Sequence[tuple]) -> List[tuple]:
    """The span records among ``records``: a program from before spans
    keeps other tuples in its trace, which no reader reads."""
    return [r for r in records if len(r) == 6]


def of(run) -> Optional[Spans]:
    """The spans of ``run``'s window, or ``None`` where it kept none."""
    records = shaped(getattr(run, "spans", None) or ())
    return Spans(records, run.win.t0, run.win.t1) if records else None


# ------------------------------------------------- on the profiler's clock
def align(host: Sequence[Sequence], steps: Sequence[tuple]
          ) -> Optional[Tuple[float, float]]:
    """``(offset_ns, error_ns)``: the profiler's time of a host instant is
    its ``monotonic_ns`` plus ``offset_ns``, the median over the traced
    ``bench.step#i`` of the span's start in the trace minus
    ``steps[i][0]`` (``harness.Recorder.step``, seconds); ``error_ns`` is
    the largest deviation from it.  ``None`` without a traced step."""
    diffs = []
    for name, start, _ in host:
        label, _, idx = name.partition("#")
        if label == STEP_SPAN and idx and int(idx) < len(steps):
            diffs.append(start - steps[int(idx)][0] * 1e9)
    if not diffs:
        return None
    off = statistics.median(diffs)
    return off, max(abs(d - off) for d in diffs)


def _label(rec: tuple) -> str:
    return rec[5]["task"] if rec[0] == "edat.task" else rec[0]


def idle_by_span(ev: Dict[str, Any], records: Sequence[tuple],
                 offset_ns: float) -> List[Tuple[str, int]]:
    """The traced window's device idle time (ns), as ``trace.reduce``
    counts it, summed by the innermost program span open at the start of
    each gap: the deepest, then the latest opened, of those open on any
    thread.  An ``edat.task`` is labelled by its task's name; a gap with
    no span open by ``host.other``.  Every label, most idle first."""
    w0, w1 = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW_SPAN][0]
    dev = min(d for d, *_ in ev["device"])
    busy = _union([(max(s, w0), min(s + d, w1))
                   for dv, line, _, s, d in ev["device"]
                   if dv == dev and line == OPS and s < w1 and s + d > w0])
    gaps, prev = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    by_id = {r[3]: r for r in records}
    depth: Dict[int, int] = {}

    def depth_of(r):
        if r[3] not in depth:
            up = by_id.get(r[4])
            depth[r[3]] = 0 if up is None else depth_of(up) + 1
        return depth[r[3]]

    # sweep: spans by start, gaps by start, the open set between them
    ordered = sorted(records, key=lambda r: r[1])
    out: Dict[str, int] = {}
    live: List[tuple] = []
    j = 0
    for s, e in gaps:
        t = s - offset_ns
        while j < len(ordered) and ordered[j][1] <= t:
            live.append(ordered[j])
            j += 1
        live = [r for r in live if r[2] > t]
        label = (_label(max(live, key=lambda r: (depth_of(r), r[1])))
                 if live else "host.other")
        out[label] = out.get(label, 0) + (e - s)
    return sorted(out.items(), key=lambda kv: -kv[1])


def breakdown(ev: Dict[str, Any], records: Sequence[tuple],
              steps: Sequence[tuple]) -> Optional[Dict[str, Any]]:
    """The traced window's spans on the profiler's clock: the offset, the
    alignment error and the ``TOP`` labels of ``idle_by_span`` (seconds),
    with the idle time they came from; ``None`` without spans or a traced
    step."""
    records = shaped(records)
    al = align(ev["host"], steps) if records else None
    if al is None:
        return None
    idle = idle_by_span(ev, records, al[0])
    return {"offset_ns": al[0], "align_error_ns": al[1],
            "idle_s": sum(t for _, t in idle) * 1e-9,
            "idle_by_span": [[n, t * 1e-9] for n, t in idle[:TOP]]}
