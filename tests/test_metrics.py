"""Per-channel runtime metrics: counters, merge, trace, Session.stats().

Covers the always-on observability layer end to end:

* ``payload_nbytes`` — the fire path's cheap size estimate;
* ``merge_metrics`` — folding per-process snapshots (sums, high-water
  marks, peer re-keying);
* inproc and socket ``Session.stats()`` carry the canonical
  ``channels`` / ``ranks`` / ``transport`` sections with exact counts
  for a deterministic program;
* ``metrics=False`` really turns the structured sections off;
* ``trace=True`` records bounded per-rank spans: task executions, lock
  waits and the program's own ``edat.span`` calls.
"""
import threading
import time

import numpy as np
import pytest

from repro import edat
from repro.core.metrics import RunStats, merge_metrics, payload_nbytes

pytestmark = pytest.mark.timeout(120)


# ----------------------------------------------------------- payload sizing
def test_payload_nbytes_shapes():
    assert payload_nbytes(None) == 0
    assert payload_nbytes(7) == 8
    assert payload_nbytes(1.5) == 8
    assert payload_nbytes(True) == 8
    assert payload_nbytes(1 + 2j) == 16
    assert payload_nbytes("abcd") == 4
    assert payload_nbytes(b"x" * 100) == 100
    assert payload_nbytes(np.zeros(10, dtype=np.float64)) == 80
    assert payload_nbytes([1, 2.0, "abc"]) == 8 + 8 + 3
    assert payload_nbytes({"a": 5, "b": b"xy"}) == 8 + 2
    assert payload_nbytes(object()) == 64          # flat fallback


# ----------------------------------------------------------------- RunStats
def test_runstats_is_a_callable_dict():
    s = RunStats({"run_seconds": 0.5})
    assert s["run_seconds"] == 0.5
    assert s() is s                      # s.stats() and s.stats both work
    assert isinstance(s, dict)


# ------------------------------------------------------------ merge_metrics
def test_merge_metrics_sums_and_rekeys_peers():
    p0 = {"channels": {"g": {"fires": 10, "bytes": 100, "wire_fires": 10,
                             "deliveries": 0, "consumed": 0,
                             "queued_max": 3}},
          "ranks": {0: {"tasks_executed": 2, "busy_s": 0.1,
                        "quorum_wait_s": 0.0}},
          "transport": {"kind": "socket", "coalesce": True,
                        "wire_events_sent": 10, "wire_events_recv": 0,
                        "wire_bytes": 500, "writes": 2, "dropped": 0,
                        "sendq_max": 4, "peers": {1: {"sent": 10}}}}
    p1 = {"channels": {"g": {"fires": 0, "bytes": 0, "wire_fires": 0,
                             "deliveries": 10, "consumed": 10,
                             "queued_max": 7}},
          "ranks": {1: {"tasks_executed": 10, "busy_s": 0.4,
                        "quorum_wait_s": 0.2}},
          "transport": {"kind": "socket", "coalesce": True,
                        "wire_events_sent": 0, "wire_events_recv": 10,
                        "wire_bytes": 40, "writes": 1, "dropped": 0,
                        "sendq_max": 1, "peers": {0: {"sent": 0}}}}
    m = merge_metrics([(0, p0), (1, p1)])
    g = m["channels"]["g"]
    assert g["fires"] == 10 and g["deliveries"] == 10 and g["consumed"] == 10
    assert g["queued_max"] == 7                    # max, not sum
    assert m["ranks"][1]["tasks_executed"] == 10
    assert m["ranks"][1]["quorum_wait_s"] == 0.2
    t = m["transport"]
    assert t["wire_events_sent"] == 10 and t["wire_events_recv"] == 10
    assert t["wire_bytes"] == 540 and t["writes"] == 3
    assert t["sendq_max"] == 4                     # max, not sum
    assert set(t["peers"]) == {"0->1", "1->0"}     # re-keyed by lead rank


def test_merge_metrics_skips_empty_parts():
    assert merge_metrics([(0, {})]) == {"channels": {}, "ranks": {},
                                        "transport": {}}


# ------------------------------------------------- inproc session counters
def _fanout_main(ctx, n=50):
    if ctx.rank == 0:
        ctx.submit_persistent(lambda c, e: None, deps=[(1, "x")])
    else:
        for i in range(n):
            ctx.fire(0, "x", i)


def test_inproc_stats_channels_exact():
    with edat.Session(2) as s:
        s.run(_fanout_main)
        ch = s.stats()["channels"]["x"]
    assert ch["fires"] == 50
    assert ch["bytes"] == 50 * 8                   # int payloads
    assert ch["wire_fires"] == 0                   # all ranks co-located
    assert ch["deliveries"] == 50 and ch["consumed"] == 50
    assert 1 <= ch["queued_max"] <= 50
    tr = s.stats()["transport"]
    assert tr["kind"] == "inproc"


def test_inproc_rank_section_counts_tasks():
    with edat.Session(2) as s:
        s.run(_fanout_main)
        ranks = s.stats()["ranks"]
    assert set(ranks) == {0, 1}
    # rank 0 ran the 50 sink instances (plus nothing on rank 1)
    assert ranks[0]["tasks_executed"] == 50
    assert ranks[0]["busy_s"] >= 0.0


def test_metrics_off_omits_structured_sections():
    with edat.Session(2, metrics=False) as s:
        s.run(_fanout_main)
        stats = s.stats()
    assert "run_seconds" in stats
    assert "channels" not in stats and "transport" not in stats


def test_trace_records_task_and_recv_timelines():
    """One ``edat.task`` span per execution, named by its task, ready
    before it started; no per-delivery records."""
    def main(ctx):
        if ctx.rank == 0:
            ctx.submit_persistent(lambda c, e: None, deps=[(1, "x")],
                                  name="sink")
        else:
            for i in range(50):
                ctx.fire(0, "x", i)

    t_start = time.monotonic_ns()
    with edat.Session(2, trace=True) as s:
        s.run(main)
        ranks = s.stats()["ranks"]
    trace0 = ranks[0]["trace"]
    tasks = [rec for rec in trace0 if rec[5].get("task") == "sink"]
    assert len(tasks) == 50
    assert {rec[0] for rec in trace0} == {"edat.task"}
    # (name, t0_ns, t1_ns, span_id, parent_id, attrs) on monotonic_ns
    for name, t0, t1, sid, parent, attrs in tasks:
        assert t_start <= attrs["ready_ns"] <= t0 <= t1
        assert sid > 0 and parent == 0
    assert len({rec[3] for rec in trace0}) == len(trace0)
    assert ranks[0]["trace_dropped"] == 0


def test_trace_off_by_default():
    with edat.Session(2) as s:
        s.run(_fanout_main)
        assert "trace" not in s.stats()["ranks"][0]


def _traced(main, ranks=1, **kw):
    with edat.Session(ranks, trace=True, **kw) as s:
        s.run(main)
        st = s.stats()["ranks"]
    return [rec for r in st for rec in st[r]["trace"]], st


def test_spans_nest_under_the_running_task():
    def main(ctx):
        def task(c, e):
            with edat.span("outer", k=1) as sp:
                with edat.span("inner"):
                    pass
                with edat.span("sibling"):
                    pass
                sp.set(n=2)     # attrs known only once the work is done
        ctx.submit(task, name="work")

    spans, _ = _traced(main)
    by = {rec[0]: rec for rec in spans}
    assert set(by) == {"edat.task", "outer", "inner", "sibling"}
    task, outer = by["edat.task"], by["outer"]
    assert task[5]["task"] == "work" and task[4] == 0
    assert outer[4] == task[3] and outer[5] == {"k": 1, "n": 2}
    assert by["inner"][4] == outer[3] and by["sibling"][4] == outer[3]
    for rec in (outer, by["inner"], by["sibling"]):
        parent = {r[3]: r for r in spans}[rec[4]]
        assert parent[1] <= rec[1] <= rec[2] <= parent[2]


def test_lock_wait_span_only_when_contended():
    """Two tasks on two workers take one named lock: the one that had to
    wait records ``edat.lock_wait`` under its open span; the uncontended
    acquisition records nothing."""
    held = threading.Event()

    def main(ctx):
        def first(c, e):
            c.lock("L")
            held.set()
            time.sleep(0.05)

        def second(c, e):
            held.wait(5)
            with edat.span("work"):
                c.lock("L")

        ctx.submit(first, name="first")
        ctx.submit(second, name="second")

    spans, _ = _traced(main, workers_per_rank=2)
    waits = [rec for rec in spans if rec[0] == "edat.lock_wait"]
    assert len(waits) == 1
    (name, t0, t1, sid, parent, attrs), = waits
    assert attrs == {"lock": "L"} and t1 - t0 >= 10_000_000
    work = [rec for rec in spans if rec[0] == "work"][0]
    assert parent == work[3]


def test_span_without_tracing_is_the_shared_noop():
    from repro.core import trace
    seen = []

    def main(ctx):
        def task(c, e):
            seen.append(edat.span("x", a=1))
            with seen[-1] as sp:
                c.lock("L")
                sp.set(b=2)
        ctx.submit(task)

    with edat.Session(1) as s:
        s.run(main)
        assert "trace" not in s.stats()["ranks"][0]
    assert seen == [trace.NO_SPAN]
    assert edat.span("outside a task") is trace.NO_SPAN


def test_trace_cap_counts_drops(monkeypatch):
    from repro.core import trace
    monkeypatch.setattr(trace, "TRACE_CAP", 7)

    def main(ctx):
        for _ in range(10):
            ctx.submit(lambda c, e: None)

    spans, ranks = _traced(main)
    assert len(spans) == 7
    assert ranks[0]["trace_dropped"] == 3


# ---------------------------------------------------- socket session merge
def test_socket_stats_merge_wire_counters():
    with edat.Session(2, transport="socket", timeout=120) as s:
        s.run(_fanout_main)
        stats = s.stats()
    ch = stats["channels"]["x"]
    assert ch["fires"] == 50 and ch["wire_fires"] == 50
    assert ch["deliveries"] == 50 and ch["consumed"] == 50
    t = stats["transport"]
    assert t["kind"] == "socket" and t["coalesce"] is True
    assert t["wire_events_sent"] == 50 and t["wire_events_recv"] == 50
    assert t["loopback_events"] == 0 and t["dropped"] == 0
    assert t["wire_bytes"] > 0 and t["writes"] >= 1
    assert set(t["peers"]) == {"0->1", "1->0"}
    assert stats["ranks"][0]["tasks_executed"] == 50


def _coloc_main(ctx):
    partner = ctx.rank ^ 1            # co-located under procs=2 packing
    far = (ctx.rank + 2) % 4
    ctx.submit_persistent(lambda c, e: None, deps=[(partner, "co")])
    ctx.submit_persistent(lambda c, e: None, deps=[(far, "fa")])
    for _ in range(10):
        ctx.fire(partner, "co", 1)
        ctx.fire(far, "fa", 1)


def test_socket_colocated_ranks_count_loopback():
    """4 ranks packed 2-per-process: fires between co-located ranks are
    loopback (no wire), fires across processes are wire."""
    with edat.Session(4, transport="socket", procs=2, timeout=120) as s:
        s.run(_coloc_main)
        stats = s.stats()
    assert stats["channels"]["co"]["wire_fires"] == 0
    assert stats["channels"]["fa"]["wire_fires"] == 40
    t = stats["transport"]
    assert t["wire_events_sent"] == 40
    assert t["loopback_events"] == 40
