"""The stall watch: a stretch in which the process holds its watch thread
back is recorded with the CPU time the process spent in it, and each
garbage collection is counted by generation."""
from __future__ import annotations

import gc
import sys
import time

import _chipbench  # noqa: F401
from chip import stalls


def test_a_held_interpreter_is_a_stall_with_its_cpu_time():
    watch = stalls.StallWatch()
    old = sys.getswitchinterval()
    watch.start()
    try:
        time.sleep(0.2)
        t0 = time.monotonic()
        # the watch thread cannot take the interpreter back for a second
        sys.setswitchinterval(1.0)
        while time.monotonic() - t0 < 0.6:
            pass
    finally:
        sys.setswitchinterval(old)
        time.sleep(0.2)
        watch.stop()
    t1 = t0 + 0.6
    got = [s for s in watch.summary(t0 - 1.0, time.monotonic())["stalls"]
           if s["t0"] <= t0 + 0.1 and s["t1"] >= t1]
    assert len(got) == 1
    s = got[0]
    assert s["wall_s"] >= 0.5
    assert s["user_s"] + s["sys_s"] >= 0.2
    assert s["gc_s"] < 0.1


def test_collections_counted_by_generation():
    watch = stalls.StallWatch()
    watch.start()
    t0 = time.monotonic()
    gc.collect()
    gc.collect(0)
    watch.stop()
    by_gen = watch.summary(t0, time.monotonic())["gc"]
    assert by_gen[2][0] >= 1 and by_gen[0][0] >= 1
    assert all(n >= 1 and 0 <= top <= total for n, total, top in
               by_gen.values())
    assert watch._on_gc not in gc.callbacks
