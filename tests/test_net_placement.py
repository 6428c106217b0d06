"""Multi-rank-per-process placement over SocketTransport.

What must hold when one OS process hosts several EDAT ranks:

* co-located ranks exchange events **without touching a socket** — wire
  counters stay at zero for co-located columns while the ordinary
  sent/recv vectors show the traffic (loopback shortcutting);
* remote traffic between the same processes still flows and counts on
  the wire;
* a SIGKILLed process surfaces RANK_FAILED for **every** rank it hosted,
  at every surviving rank;
* the bootstrap placement exchange handles uneven rank/process splits.
"""
import functools
import os
import socket
import threading
import time

import pytest

import _chaos as chaos
from repro import edat
from repro.core.transport import EVENT, Message
from repro.net import SocketTransport
from repro.net.launch import (ProcessGroup, default_placement,
                              launch_processes)

pytestmark = pytest.mark.timeout(120)

PLACEMENT = {0: (0, 1), 2: (2, 3)}


def _pair_2x2(**kw):
    """Two SocketTransports, two ranks each, one socket between them."""
    a, b = socket.socketpair()
    ta = SocketTransport(0, 4, {2: a}, local_ranks=(0, 1),
                         placement=PLACEMENT, **kw)
    tb = SocketTransport(2, 4, {0: b}, local_ranks=(2, 3),
                         placement=PLACEMENT, **kw)
    return ta, tb


def _ev(src, dst, eid, data=None):
    return Message(EVENT, src, dst, edat.Event(data=data, source=src,
                                               eid=eid))


def test_default_placement_blocks():
    assert default_placement(4, 2) == [(0, 1), (2, 3)]
    assert default_placement(5, 3) == [(0, 1), (2, 3), (4,)]
    assert default_placement(3, 3) == [(0,), (1,), (2,)]


# --------------------------------------------------- transport-level unit
def test_colocated_send_is_loopback_zero_wire():
    """Events between co-located ranks land in the destination inbox with
    zero socket frames; the Mattern vectors still account for them."""
    ta, tb = _pair_2x2()
    try:
        for i in range(10):
            assert ta.send(_ev(0, 1, "co", i))
        ta.send_many([_ev(1, 0, "oc", i) for i in range(5)])
        got = [m.payload.data for m in ta.drain(1)]
        assert got == list(range(10))            # FIFO, instantly available
        assert len(ta.drain(0)) == 5
        assert ta.sent_vector()[:2] == [5, 10]   # by dst: loopback counts
        assert ta.recv_vector()[:2] == [10, 5]   # by src: both popped
        assert ta.wire_sent_vector() == [0, 0, 0, 0]   # ...but not on wire
        assert ta.wire_recv_vector() == [0, 0, 0, 0]
        assert tb.wire_recv_vector() == [0, 0, 0, 0]
    finally:
        ta.close()
        tb.close()


def test_remote_send_shares_one_socket_and_counts_wire():
    """All four cross-process (src,dst) pairs flow over the single
    process-pair connection, keep per-pair FIFO, and count as wire."""
    ta, tb = _pair_2x2()
    try:
        for i in range(8):
            assert ta.send(_ev(0, 2, "x", i))
            assert ta.send(_ev(0, 3, "x", i))
            assert ta.send(_ev(1, 3, "x", i))
        deadline = time.monotonic() + 10
        got2, got3 = [], []
        while (len(got2) + len(got3)) < 24 and time.monotonic() < deadline:
            got2 += [m for m in tb.recv_many(2, timeout=0.5)]
            got3 += [m for m in tb.drain(3)]
        assert [m.payload.data for m in got2] == list(range(8))
        by_src = {0: [], 1: []}
        for m in got3:
            by_src[m.src].append(m.payload.data)
        assert by_src[0] == list(range(8))       # per-(src,dst) FIFO
        assert by_src[1] == list(range(8))
        assert ta.wire_sent_vector() == [0, 0, 8, 16]
        assert tb.wire_recv_vector() == [16, 8, 0, 0]
        assert tb.recv_vector() == [16, 8, 0, 0]
    finally:
        ta.close()
        tb.close()


def test_mark_dead_one_colocated_rank_keeps_socket():
    """Marking ONE rank of a remote process dead must not sever the
    connection its co-located survivor still uses."""
    ta, tb = _pair_2x2()
    try:
        ta.mark_dead(3)
        assert ta.is_dead(3) and not ta.is_dead(2)
        assert not ta.send(_ev(0, 3, "x"))       # dropped
        assert ta.dropped == 1
        assert ta.send(_ev(0, 2, "x", 7))        # still flows
        deadline = time.monotonic() + 10
        got = []
        while not got and time.monotonic() < deadline:
            got = tb.recv_many(2, timeout=0.5)
        assert got[0].payload.data == 7
        ta.mark_dead(2)                          # now the whole process is
        assert not ta.send(_ev(0, 2, "y"))       # gone: socket severed
    finally:
        ta.close()
        tb.close()


def test_dead_process_reports_every_hosted_rank():
    """A crashed peer process (no BYE) yields one on_peer_dead callback
    per rank it hosted."""
    a, b = socket.socketpair()
    ta = SocketTransport(0, 4, {2: a}, local_ranks=(0, 1),
                        placement=PLACEMENT)
    tb = SocketTransport(2, 4, {0: b}, local_ranks=(2, 3),
                        placement=PLACEMENT)
    deaths = []
    ta.on_peer_dead = deaths.append
    chaos.crash_socket(b)
    chaos.wait_for(lambda: len(deaths) >= 2, 10, desc="both rank deaths")
    assert sorted(deaths) == [2, 3]
    assert ta.is_dead(2) and ta.is_dead(3)
    ta.close()
    tb.close()


# ------------------------------------ full runtimes, one process (threads)
def test_colocated_runtime_exchange_zero_wire_frames():
    """Acceptance: a 4-rank world on 2 transports — every rank streams
    events to its co-located partner AND to a remote rank.  Co-located
    columns of the wire counters must end at exactly zero while the
    event flow itself is verified by the sinks."""
    N = 40
    ta, tb = _pair_2x2()
    rts = [edat.Runtime(4, transport=ta, unconsumed="ignore"),
           edat.Runtime(4, transport=tb, unconsumed="ignore")]
    got = {r: {"co": [], "far": []} for r in range(4)}

    def main(ctx):
        partner = ctx.rank ^ 1               # co-located buddy
        far = (ctx.rank + 2) % 4             # remote process

        def co_sink(c, events):
            got[c.rank]["co"].append(events[0].data)

        def far_sink(c, events):
            got[c.rank]["far"].append(events[0].data)

        ctx.submit_persistent(co_sink, deps=[(partner, "co")])
        ctx.submit_persistent(far_sink, deps=[(far, "far")])
        for i in range(N):
            ctx.fire(partner, "co", i)
            ctx.fire(far, "far", i)

    results = [None, None]

    def go(i):
        results[i] = rts[i]._run_internal(main, timeout=60)

    ths = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(90)
        assert not t.is_alive(), "placement run wedged"
    for r in range(4):
        assert got[r]["co"] == list(range(N))
        assert got[r]["far"] == list(range(N))
    for t in (ta, tb):
        ws, wr = t.wire_sent_vector(), t.wire_recv_vector()
        s, rv = t.sent_vector(), t.recv_vector()
        for r in t.local_ranks:
            # nothing to/from a co-located rank ever hit the socket...
            assert ws[r] == 0 and wr[r] == 0, (t.rank, ws, wr)
        for r in range(4):
            if r not in t.local_ranks:
                # ...while every remote column did
                assert ws[r] == N and wr[r] == N, (t.rank, ws, wr)
            # and the Mattern accounting covers both kinds of traffic
            assert s[r] >= N and rv[r] >= N


def test_colocated_fire_and_forget_snapshot():
    """Regression: a non-ref fire to a CO-LOCATED rank must snapshot at
    fire time.  The serialising transport's wire pickle never happens on
    the loopback path, so the runtime has to keep its defensive copy —
    mutating the payload right after ctx.fire must not be observable."""
    got = {}
    ta, tb = _pair_2x2()
    rts = [edat.Runtime(4, transport=ta, unconsumed="ignore"),
           edat.Runtime(4, transport=tb, unconsumed="ignore")]

    def main(ctx):
        if ctx.rank == 0:
            buf = {"v": [1, 2, 3]}
            ctx.fire(1, "e", buf)            # co-located, no ref
            buf["v"][:] = [99, 99, 99]       # post-fire mutation
        elif ctx.rank == 1:
            ctx.submit(lambda c, evs: got.setdefault(
                "v", list(evs[0].data["v"])), deps=[(0, "e")])

    results = [None, None]

    def go(i):
        results[i] = rts[i]._run_internal(main, timeout=30)

    ths = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(45)
        assert not t.is_alive()
    assert got["v"] == [1, 2, 3], "loopback leaked the live payload"


# ------------------------------------------------- real spawned processes
_READY_RANK = 3


def _placement_kill_main(ctx, ready_path="", out_dir=""):
    """4 ranks / 2 procs: the victim process (ranks 2,3) stalls; each
    surviving rank writes a marker file once it has seen RANK_FAILED for
    BOTH hosted ranks of the victim."""
    seen = set()

    def on_fail(c, events):
        seen.add(events[0].data)
        if seen == {2, 3}:
            open(os.path.join(out_dir, f"failed_seen_{c.rank}"),
                 "w").close()

    ctx.submit_persistent(on_fail, deps=[(edat.ANY, edat.RANK_FAILED)])
    if ctx.rank == _READY_RANK:
        open(ready_path, "w").close()
        time.sleep(300)          # never finishes: must be SIGKILLed


def test_killed_process_surfaces_rank_failed_for_all_hosted_ranks(tmp_path):
    """SIGKILL one process of a 4-rank/2-process world: both survivors
    must observe RANK_FAILED for *both* ranks the victim hosted, then
    terminate cleanly."""
    ready = str(tmp_path / "ready")
    pg = ProcessGroup(
        4, functools.partial(_placement_kill_main, ready_path=ready,
                             out_dir=str(tmp_path)),
        n_procs=2, run_timeout=60, hb_interval=0.2, hb_timeout=1.5)
    pg.start()
    chaos.sigkill_when_ready(pg, 2, ready, timeout=60, settle=0.3)
    stats = pg.wait(60)
    codes = pg.exitcodes()
    assert codes[2] != 0 and codes[3] != 0       # the victim pair
    assert codes[0] == 0 and codes[1] == 0       # survivors exited clean
    for r in (0, 1):
        assert os.path.exists(str(tmp_path / f"failed_seen_{r}")), \
            f"rank {r} did not see RANK_FAILED for both hosted ranks"
    # 2 RANK_FAILED handler runs per surviving rank
    assert stats["tasks_executed"] == 4


def _ring_main(ctx, n_hops=60):
    left = (ctx.rank - 1) % ctx.n_ranks

    def relay(c, events):
        if events[0].data < n_hops:
            c.fire((c.rank + 1) % c.n_ranks, "token", events[0].data + 1)

    ctx.submit_persistent(relay, deps=[(left, "token")])
    if ctx.rank == 0:
        ctx.fire(1, "token", 1)


def test_uneven_placement_spawned_ring():
    """5 ranks over 3 processes (blocks (0,1)(2,3)(4,)): the rendezvous
    exchanges the placement and the ring crosses both loopback and
    socket hops."""
    stats = launch_processes(5, functools.partial(_ring_main, n_hops=60),
                             n_procs=3, timeout=60)
    assert stats["events_sent"] == stats["events_received"] == 60
    assert stats["tasks_executed"] == 60


# ------------------------------------------------- one chip per rank process
def _record_chip_main(ctx, out_dir):
    path = os.path.join(out_dir, f"rank{ctx.rank}")
    with open(path, "w") as f:
        f.write(os.environ.get("TPU_VISIBLE_CHIPS", "none"))


def test_rank_processes_are_pinned_to_their_own_chip(monkeypatch, tmp_path):
    """On a TPU host each spawned process gets libtpu's visibility
    variables for chip ``p`` before it runs any user code; on a host
    without chips nothing is set."""
    from repro.net import launch
    pg = ProcessGroup(5, "unused:main", n_procs=3)     # (0,1) (2,3) (4,)
    monkeypatch.setattr(launch, "tpu_chip_count", lambda: 0)
    assert pg._child_env(0) == {}
    monkeypatch.setattr(launch, "tpu_chip_count", lambda: 4)
    envs = [pg._child_env(rs[0]) for rs in pg.placement]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_ADDRESSES"].endswith(":" + e["TPU_PROCESS_PORT"])
    # and the spawned processes see it (JAX_PLATFORMS=cpu keeps them off
    # any chip here)
    launch_processes(4, functools.partial(_record_chip_main,
                                          out_dir=str(tmp_path)),
                     n_procs=2, timeout=60)
    seen = {r: (tmp_path / f"rank{r}").read_text() for r in range(4)}
    assert seen == {0: "0", 1: "0", 2: "1", 3: "1"}


@pytest.mark.parametrize("platforms,chips", [
    ("cpu", 0), ("", 2), ("tpu", 2), ("tpu,cpu", 2), ("cuda,cpu", 0)])
def test_chips_are_counted_only_for_children_that_may_use_them(
        monkeypatch, platforms, chips):
    """``JAX_PLATFORMS`` without a TPU in it holds the children off the
    chips: none is counted, so none is pinned."""
    from repro.net import launch
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setattr(launch.glob, "glob", lambda pat: (
        ["/dev/accel0", "/dev/accel1"] if "accel" in pat else []))
    assert launch.tpu_chip_count() == chips


def test_launch_refuses_more_processes_than_chips(monkeypatch):
    """Two processes must never share a chip: a launch of more rank
    processes than the host has chips fails before spawning anything."""
    from repro.net import launch
    monkeypatch.setattr(launch, "tpu_chip_count", lambda: 1)
    pg = ProcessGroup(2, "unused:main", n_procs=2)
    with pytest.raises(RuntimeError, match="2 rank processes on a host "
                                           "with 1 TPU chip"):
        pg.start()
    assert pg._procs == {}
    # one process hosting both ranks fits the one chip
    assert ProcessGroup(2, "unused:main", n_procs=1)._child_env(0)[
        "TPU_VISIBLE_CHIPS"] == "0"


def _square(x):
    return x * x


def _boom():
    raise ValueError("boom")


def test_run_in_process_returns_result_and_reports_failure():
    from repro.net.launch import run_in_process
    assert run_in_process(_square, 7) == 49
    with pytest.raises(RuntimeError, match="ValueError: boom"):
        run_in_process(_boom)
