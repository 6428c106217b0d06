"""Opt-in span recorder (``Session(trace=True)``).

Each rank keeps one :class:`Recorder`: a bounded buffer of span records
``(name, t0_ns, t1_ns, span_id, parent_id, attrs)``, stamped with
``time.monotonic_ns()`` (the clock of the serving program's ``t_*``
records), read out through ``Session.stats()["ranks"][r]["trace"]``.
Beyond ``TRACE_CAP`` records a rank counts drops (``trace_dropped``)
instead of storing, so memory stays bounded on long runs.

The scheduler records one ``edat.task`` span per task execution (attrs
``task`` and ``ready_ns``, when the instance became ready) and one
``edat.lock_wait`` span per named-lock acquisition that had to wait
(attr ``lock``).  Program code adds its own spans with :func:`span`,
without a ``Context``: the running task's recorder and innermost open
span live in a thread-local that the scheduler sets around each task.
With tracing off nothing is installed there, so :func:`span` returns the
shared :data:`NO_SPAN` and no clock is read.

Span ids are unique within a process (``parent_id`` 0: no parent), so
the traces of the ranks of one process can be merged into one list.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

#: per-rank cap on span records; beyond it, records are counted
#: (``trace_dropped``) instead of stored
TRACE_CAP = 50_000

_ids = itertools.count(1)


class Recorder:
    """One rank's span buffer."""

    def __init__(self):
        self.records: List[tuple] = []
        self.dropped = 0
        self._mu = threading.Lock()

    def add(self, name: str, t0: int, t1: int, sid: int, parent: int,
            attrs: Dict[str, Any]) -> None:
        with self._mu:
            if len(self.records) < TRACE_CAP:
                self.records.append((name, t0, t1, sid, parent, attrs))
            else:
                self.dropped += 1

    def snapshot(self) -> Tuple[List[tuple], int]:
        with self._mu:
            return list(self.records), self.dropped


class _Here(threading.local):
    """The calling thread's recorder (set while a traced task runs) and
    its innermost open span (0: none)."""

    rec: Optional[Recorder] = None
    cur: int = 0


_here = _Here()


class Span:
    """A span being recorded: opened by ``__enter__``, recorded with its
    parent (the span open on this thread before it) by ``__exit__``."""

    __slots__ = ("rec", "name", "attrs", "sid", "parent", "t0")

    def __init__(self, rec: Recorder, name: str, attrs: Dict[str, Any]):
        self.rec = rec
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "Span":
        self.sid = next(_ids)
        self.parent = _here.cur
        _here.cur = self.sid
        self.t0 = time.monotonic_ns()
        return self

    def set(self, **attrs: Any) -> None:
        """Add ``attrs`` to the span's record."""
        self.attrs.update(attrs)

    def __exit__(self, *exc) -> bool:
        t1 = time.monotonic_ns()
        _here.cur = self.parent
        self.rec.add(self.name, self.t0, t1, self.sid, self.parent,
                     self.attrs)
        return False


class _NoSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def set(self, **attrs: Any) -> None:
        pass

    def __exit__(self, *exc) -> bool:
        return False


#: what :func:`span` returns where nothing is recorded
NO_SPAN = _NoSpan()


def span(name: str, **attrs: Any):
    """A context manager recording ``name`` with ``attrs`` as a child of
    the innermost span open on this thread; :data:`NO_SPAN` outside a
    traced task."""
    rec = _here.rec
    return NO_SPAN if rec is None else Span(rec, name, attrs)


def begin_task(rec: Recorder) -> Tuple[int, int]:
    """Install ``rec`` on this thread for a task's execution; returns the
    task span's ``(span_id, t0_ns)`` for :func:`end_task`."""
    sid = next(_ids)
    _here.rec = rec
    _here.cur = sid
    return sid, time.monotonic_ns()


def end_task(rec: Recorder, sid: int, t0: int, task: str,
             ready_ns: int) -> None:
    """Record the ``edat.task`` span opened by :func:`begin_task` and
    uninstall the recorder."""
    _here.rec = None
    _here.cur = 0
    rec.add("edat.task", t0, time.monotonic_ns(), sid, 0,
            {"task": task, "ready_ns": ready_ns})


def record_since(rec: Recorder, name: str, t0: int,
                 attrs: Dict[str, Any]) -> None:
    """Record ``name`` from ``t0`` to now, as a child of the innermost
    span open on this thread."""
    rec.add(name, t0, time.monotonic_ns(), next(_ids), _here.cur, attrs)
