"""Host stalls of the benchmark's process, and what the process did in them.

A watch thread wakes every ``PERIOD`` seconds.  A wake-up later than
``THRESHOLD`` past its due time is a stall: for each it records how long,
the process's user and system CPU time and involuntary context switches
in it (``getrusage``), and the time Python's garbage collector ran in it
(``gc.callbacks``).  A stall with the process's CPU busy or the collector
running is the process's own; one in which the process used next to no
CPU time is a wait: on a call that holds the interpreter, or on the host.
"""
from __future__ import annotations

import gc
import resource
import threading
import time
from typing import Any, Dict, List, Optional

PERIOD = 0.05
THRESHOLD = 0.25


def _usage():
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime, r.ru_stime, r.ru_nivcsw


class StallWatch:
    """Stalls and garbage collections between ``start()`` and ``stop()``,
    on the clock of the program's records (``time.monotonic``)."""

    def __init__(self):
        self.stalls: List[Dict[str, float]] = []
        self.collections: List[tuple] = []   # (t0, seconds, generation)
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self.collections.append((self._gc_t0,
                                     time.monotonic() - self._gc_t0,
                                     info["generation"]))

    def _run(self) -> None:
        last, use = time.monotonic(), _usage()
        while not self._stop.wait(PERIOD):
            now, use2 = time.monotonic(), _usage()
            if now - last - PERIOD > THRESHOLD:
                self.stalls.append({
                    "t0": last, "t1": now, "wall_s": now - last,
                    "user_s": use2[0] - use[0], "sys_s": use2[1] - use[1],
                    "nivcsw": use2[2] - use[2]})
            last, use = now, use2

    def start(self) -> None:
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="stall-watch")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def summary(self, t0: float, t1: float) -> Dict[str, Any]:
        """What fell in ``[t0, t1)``: each stall with the collector's time
        inside it, and the collections by generation."""
        cols = [c for c in self.collections if t0 <= c[0] < t1]
        stalls = []
        for s in self.stalls:
            if not (t0 <= s["t1"] and s["t0"] < t1):
                continue
            gc_s = sum(max(0.0, min(c0 + d, s["t1"]) - max(c0, s["t0"]))
                       for c0, d, _ in self.collections)
            stalls.append(dict(s, gc_s=gc_s, at_s=s["t0"] - t0))
        by_gen = {}
        for _, d, g in cols:
            n, tot, top = by_gen.get(g, (0, 0.0, 0.0))
            by_gen[g] = (n + 1, tot + d, max(top, d))
        return {"stalls": stalls, "gc": by_gen}
