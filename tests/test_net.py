"""repro.net: socket transport, rendezvous, launcher, failure detection.

Three layers of coverage, cheapest first:

* transport-level unit tests over ``socket.socketpair()`` ends (no
  processes, no rendezvous);
* two full Runtimes over a socket pair *in one process* (threads), which
  exercises the whole distributed CONTROL protocol — status polls,
  terminate broadcast, abort propagation — without spawn overhead;
* real ``multiprocessing`` spawn runs through :mod:`repro.net.launch`,
  including a SIGKILL detected by the heartbeat/EOF failure detector.

Every cross-process test is guarded by the launcher's own join deadline
(and by pytest-timeout where installed): a hang fails, it never wedges CI.
"""
import functools
import os
import socket
import sys
import threading
import time

import pytest

import _chaos as chaos
from repro import edat
from repro.core.transport import CONTROL, EVENT, Message, Transport
from repro.net import SocketTransport, bootstrap
from repro.net.launch import ProcessGroup, launch_processes

pytestmark = pytest.mark.timeout(120)


def _pair(n_ranks=2, **kw):
    """Two SocketTransports joined by an AF_UNIX stream pair."""
    a, b = socket.socketpair()
    ta = SocketTransport(0, n_ranks, {1: a}, **kw)
    tb = SocketTransport(1, n_ranks, {0: b}, **kw)
    return ta, tb


def _ev(src, dst, eid, data=None):
    return Message(EVENT, src, dst, edat.Event(data=data, source=src,
                                               eid=eid))


# ------------------------------------------------------------ unit: framing
def test_socket_transport_fifo_and_batching():
    ta, tb = _pair()
    try:
        for i in range(20):
            assert ta.send(_ev(0, 1, "seq", i))
        ta.send_many([_ev(0, 1, "seq", i) for i in range(20, 40)])
        got = []
        deadline = time.monotonic() + 10
        while len(got) < 40 and time.monotonic() < deadline:
            got += [m.payload.data for m in tb.recv_many(1, timeout=1.0)]
        assert got == list(range(40))            # per-(src,dst) FIFO
        assert ta.sent_vector() == [0, 40]
        assert tb.recv_vector() == [40, 0]
        assert tb.pending(1) == 0
    finally:
        ta.close()
        tb.close()


def test_socket_transport_loopback_and_drain():
    ta, tb = _pair()
    try:
        ta.send_many([_ev(0, 0, "self", i) for i in range(5)])
        assert ta.pending(0) == 5
        msgs = ta.drain(0, max_n=3)
        assert [m.payload.data for m in msgs] == [0, 1, 2]
        assert [m.payload.data for m in ta.drain(0)] == [3, 4]
        assert ta.sent_vector()[0] == 5 and ta.recv_vector()[0] == 5
    finally:
        ta.close()
        tb.close()


def test_socket_transport_notify_hook():
    ta, tb = _pair()
    hits = threading.Event()
    try:
        tb.set_notify(1, hits.set)
        ta.send(_ev(0, 1, "x"))
        assert hits.wait(5.0)
    finally:
        ta.close()
        tb.close()


def test_socket_transport_control_not_counted():
    ta, tb = _pair()
    try:
        ta.send(Message(CONTROL, 0, 1, ("poke", None)))
        deadline = time.monotonic() + 5
        while tb.pending(1) == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        tb.drain(1)
        assert ta.sent_vector() == [0, 0]        # user events only
        assert tb.recv_vector() == [0, 0]
    finally:
        ta.close()
        tb.close()


def test_validate_payload_typeerror():
    ta, tb = _pair()
    try:
        with pytest.raises(TypeError, match="not.*picklable"):
            ta.validate_payload(lambda: None)
        ta.validate_payload({"fine": [1, 2.5, "x"]})
    finally:
        ta.close()
        tb.close()


def test_clean_close_is_not_a_failure():
    ta, tb = _pair()
    deaths = []
    tb.on_peer_dead = deaths.append
    ta.close()
    time.sleep(0.3)
    tb.close()
    assert deaths == []                          # BYE suppressed the verdict


def test_abrupt_close_declares_peer_dead():
    a, b = socket.socketpair()
    ta = SocketTransport(0, 2, {1: a})
    tb = SocketTransport(1, 2, {0: b})
    deaths = []
    tb.on_peer_dead = deaths.append
    chaos.crash_socket(a)                        # simulated crash: no BYE
    deadline = time.monotonic() + 5
    while not deaths and time.monotonic() < deadline:
        time.sleep(0.01)
    assert deaths == [0] and tb.is_dead(0)
    assert not tb.send(_ev(1, 0, "x"))           # drops, counted
    assert tb.dropped == 1
    tb.close()
    ta.close()


def test_heartbeat_detects_silent_peer():
    """Pure heartbeat-timeout path: the connection stays open but rank 0
    never beats (hb_interval=0 disables its sender)."""
    a, b = socket.socketpair()
    ta = SocketTransport(0, 2, {1: a}, hb_interval=0)
    tb = SocketTransport(1, 2, {0: b}, hb_interval=0.1, hb_timeout=0.6)
    deaths = []
    tb.on_peer_dead = deaths.append
    deadline = time.monotonic() + 10
    while not deaths and time.monotonic() < deadline:
        time.sleep(0.02)
    assert deaths == [0] and tb.is_dead(0)
    tb.close()
    ta.close()


def _hold_interpreter(seconds: float) -> None:
    """Keep every other thread of this process off the interpreter for
    ``seconds``, as a native call that holds the GIL does (starting an
    accelerator backend, for one)."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(seconds + 1.0)
    try:
        end = time.monotonic() + seconds
        while time.monotonic() < end:
            pass
    finally:
        sys.setswitchinterval(old)


def test_own_stall_is_not_counted_against_peers():
    """A process frozen for longer than ``hb_timeout`` must not declare its
    peers dead when it wakes: they beat all along, it could not hear them.
    The heartbeat loop logs the stall."""
    ta, tb = _pair(hb_interval=0.1, hb_timeout=0.6)
    deaths = []
    ta.on_peer_dead = tb.on_peer_dead = deaths.append
    try:
        time.sleep(0.3)
        _hold_interpreter(1.5)
        time.sleep(1.0)                  # several beats after the stall
        assert deaths == [] and not ta.is_dead(1) and not tb.is_dead(0)
        stalls = tb.metrics()["hb_stalls"]
        assert stalls and max(s for _, s in stalls) > 1.0
    finally:
        tb.close()
        ta.close()


def test_quiet_peer_is_judged_by_its_connection_alone():
    """Inside ``quiet()`` a peer that never beats is not declared dead;
    once the window closes its silence counts again."""
    a, b = socket.socketpair()
    ta = SocketTransport(0, 2, {1: a}, hb_interval=0)      # never beats
    tb = SocketTransport(1, 2, {0: b}, hb_interval=0.1, hb_timeout=0.6)
    deaths = []
    tb.on_peer_dead = deaths.append
    try:
        with ta.quiet():
            with ta.quiet():             # windows nest into one
                time.sleep(1.5)
            time.sleep(1.0)
            assert deaths == [] and not tb.is_dead(0)
        deadline = time.monotonic() + 10
        while not deaths and time.monotonic() < deadline:
            time.sleep(0.02)
        assert deaths == [0]
        (start, end), = ta.metrics()["quiet"]
        assert end - start > 2.0
    finally:
        tb.close()
        ta.close()


def test_crash_inside_quiet_window_is_still_detected():
    """A quiet window waives the heartbeat, not the connection: a peer
    that dies inside one is declared dead at once."""
    ta, tb = _pair(hb_interval=0.1, hb_timeout=30.0)
    deaths = []
    tb.on_peer_dead = deaths.append
    try:
        with ta.quiet():
            time.sleep(0.2)
            chaos.crash_socket(ta._peers[1])
            deadline = time.monotonic() + 5
            while not deaths and time.monotonic() < deadline:
                time.sleep(0.01)
        assert deaths == [0] and tb.is_dead(0)
    finally:
        tb.close()
        ta.close()


class _FrozenBuildRing:
    """Ring relay whose construction first freezes the process hosting
    rank 1 (only it, so its peer is awake to watch the silence)."""

    def __init__(self, seconds: float, n_hops: int):
        if os.environ.get("EDAT_RANK") == "1":
            _hold_interpreter(seconds)
        self.n_hops = n_hops

    def start(self, ctx):
        _ring_main(ctx, n_hops=self.n_hops)


def test_frozen_program_build_is_not_a_death():
    """A rank process freezes for longer than ``hb_timeout`` while it
    builds its program: no rank is declared dead and the round completes.
    Session stats carry every process's quiet window and the stall."""
    with edat.Session(ranks=2, procs=2, transport="socket", timeout=60,
                      hb_interval=0.2, hb_timeout=1.0) as s:
        s.run(edat.deferred(_FrozenBuildRing, 2.5, 20))
        stats = s.stats()
    assert stats["events_sent"] == 20
    assert sorted(stats["transport"]["quiet"]) == [0, 1]
    stalls = [sec for log in stats["transport"]["hb_stalls"].values()
              for _, sec in log]
    assert max(stalls) > 1.0


# --------------------------------------------------- rendezvous (threads)
def test_bootstrap_all_pairs_mesh():
    n = 3
    coord = ("127.0.0.1", 0)
    # pre-pick a coordinator port the threads can share
    srv = socket.socket()
    srv.bind(coord)
    port = srv.getsockname()[1]
    srv.close()
    out = {}

    def boot(rank):
        t = bootstrap(rank, n, ("127.0.0.1", port), timeout=20)
        out[rank] = t

    ths = [threading.Thread(target=boot, args=(r,)) for r in range(n)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(30)
    assert sorted(out) == [0, 1, 2]
    try:
        # every ordered pair can talk
        for src in range(n):
            for dst in range(n):
                if src != dst:
                    assert out[src].send(_ev(src, dst, f"e{src}{dst}", src))
        for dst in range(n):
            seen = set()
            deadline = time.monotonic() + 10
            while len(seen) < n - 1 and time.monotonic() < deadline:
                for m in out[dst].recv_many(dst, timeout=1.0):
                    seen.add(m.payload.data)
            assert seen == set(range(n)) - {dst}
    finally:
        for t in out.values():
            t.close()


# ------------------------------- full distributed protocol, in one process
def _dual_runtime_run(main, *, n=2, progress="thread", timeout=30.0, **kw):
    """Two Runtimes over a socket pair, one thread each — the complete
    cross-process CONTROL protocol without spawn overhead."""
    ta, tb = _pair(n)
    rts = [edat.Runtime(n, transport=ta, progress=progress, **kw),
           edat.Runtime(n, transport=tb, progress=progress, **kw)]
    results = [None, None]

    def go(i):
        try:
            # transport injection is below the Session surface: drive the
            # runtime's internal entry point directly, not the v1 shim
            results[i] = ("ok", rts[i]._run_internal(main, timeout=timeout))
        except BaseException as e:  # noqa: BLE001
            results[i] = ("err", e)

    ths = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout + 15)
        assert not t.is_alive(), "distributed run wedged"
    return results


def test_distributed_pingpong_and_stats_broadcast():
    N = 50
    got = []

    def ping(ctx, events):
        if events[0].data < N:
            ctx.fire(1, "ping", events[0].data + 1)

    def pong(ctx, events):
        got.append(events[0].data)
        ctx.fire(0, "pong", events[0].data)

    def main(ctx):
        if ctx.rank == 0:
            ctx.submit_persistent(ping, deps=[(1, "pong")])
            ctx.fire(1, "ping", 1)
        else:
            ctx.submit_persistent(pong, deps=[(0, "ping")])

    res = _dual_runtime_run(main, unconsumed="ignore")
    assert [r[0] for r in res] == ["ok", "ok"]
    assert got == list(range(1, N + 1))          # FIFO across the wire
    # rank 1 received rank 0's stats via the terminate broadcast
    assert res[1][1]["events_sent"] == res[0][1]["events_sent"] > 0


def test_distributed_worker_poll_progress():
    got = []

    def sink(ctx, events):
        got.append(events[0].data)

    def main(ctx):
        if ctx.rank == 0:
            ctx.submit_persistent(sink, deps=[(1, "e")])
        else:
            for i in range(20):
                ctx.fire(0, "e", i)

    res = _dual_runtime_run(main, progress="worker")
    assert [r[0] for r in res] == ["ok", "ok"]
    assert got == list(range(20))


def test_fire_unpicklable_raises_at_fire_over_socket():
    """Satellite: a non-picklable payload fails *inside the firing task*
    with TypeError, and the run still terminates cleanly (the counters
    were never touched)."""
    outcome = {}

    def bad_then_good(ctx, events):
        try:
            ctx.fire(1, "bad", lambda: None)
        except TypeError as e:
            outcome["err"] = str(e)
            ctx.fire(1, "ok", 7)

    def sink(ctx, events):
        outcome["got"] = events[0].data

    def main(ctx):
        if ctx.rank == 0:
            ctx.submit(bad_then_good)
        else:
            ctx.submit(sink, deps=[(0, "ok")])

    res = _dual_runtime_run(main)
    assert [r[0] for r in res] == ["ok", "ok"]
    assert "picklable" in outcome["err"]
    assert outcome["got"] == 7


def test_fire_unpicklable_inproc_keeps_copy_semantics():
    """The in-proc transport still accepts anything copyable (no pickle
    requirement): same payload, no error."""
    got = []

    def sink(ctx, events):
        got.append(events[0].data())

    def main(ctx):
        if ctx.rank == 0:
            ctx.fire(1, "fn", lambda: 42, ref=True)
        else:
            ctx.submit(sink, deps=[(0, "fn")])

    with edat.Session(2, workers_per_rank=2) as s:
        s.run(main, timeout=30)
    assert got == [42]


def test_task_error_propagates_to_peer_process():
    def boom(ctx, events):
        raise ValueError("kaboom")

    def main(ctx):
        if ctx.rank == 1:
            ctx.submit(boom)

    res = _dual_runtime_run(main)
    assert [r[0] for r in res] == ["err", "err"]
    # rank 1 raised locally; rank 0 got the abort CONTROL message
    assert "kaboom" in str(res[0][1])
    assert isinstance(res[0][1], edat.EdatTaskError)


def test_timer_pending_on_remote_rank_delays_termination():
    """fire_after on rank 1 targeting rank 0: the detector (rank 0) must
    see rank 1's pending timer through the status replies and hold
    termination until the event lands."""
    got = []

    def tick(ctx, events):
        got.append(events[0].data)

    def main(ctx):
        if ctx.rank == 0:
            ctx.submit(tick, deps=[(1, "tick")])
        else:
            ctx.fire_after(0.4, 0, "tick", 9)

    res = _dual_runtime_run(main)
    assert [r[0] for r in res] == ["ok", "ok"]
    assert got == [9]


def test_deadlock_detected_across_ranks():
    def never(ctx, events):  # pragma: no cover
        pass

    def main(ctx):
        if ctx.rank == 1:
            ctx.submit(never, deps=[(0, "never")])

    res = _dual_runtime_run(main, timeout=20)
    assert [r[0] for r in res] == ["err", "err"]
    assert isinstance(res[0][1], edat.EdatDeadlockError)
    assert isinstance(res[1][1], edat.EdatDeadlockError)


def test_socket_fire_and_forget_snapshot():
    """Remote fires skip the deep-copy (the wire pickle is the snapshot):
    mutating the buffer right after ctx.fire must not be observable."""
    import numpy as np
    got = {}

    def sink(ctx, events):
        got["v"] = list(events[0].data)

    def main(ctx):
        if ctx.rank == 0:
            buf = np.array([1, 2, 3])
            ctx.fire(1, "e", buf)
            buf[:] = 99
        else:
            ctx.submit(sink, deps=[(0, "e")])

    res = _dual_runtime_run(main)
    assert [r[0] for r in res] == ["ok", "ok"]
    assert got["v"] == [1, 2, 3]


def test_mark_dead_stops_inbound_delivery():
    """mark_dead must actually sever the connection (shutdown, not a
    refcounted close): nothing sent by the dead-marked peer may be
    delivered afterwards."""
    ta, tb = _pair()
    try:
        tb.mark_dead(0)
        assert tb.is_dead(0)
        ta.send(_ev(0, 1, "late", 1))
        time.sleep(0.3)
        assert tb.pending(1) == 0
        assert tb.drain(1) == []
    finally:
        ta.close()
        tb.close()


# ----------------------------------------------- minimal-Transport fallback
class MinimalTransport(Transport):
    """The least a transport can be: send/recv/wake only.  Everything else
    — send_many, drain, recv_many, notify, failure hooks — comes from the
    Transport base class defaults."""

    def __init__(self, n_ranks):
        self._boxes = [[] for _ in range(n_ranks)]
        self._cv = threading.Condition()

    def send(self, msg):
        with self._cv:
            self._boxes[msg.dst].append(msg)
            self._cv.notify_all()
        return True

    def recv(self, rank, timeout):
        with self._cv:
            if not self._boxes[rank]:
                self._cv.wait(timeout)
            if self._boxes[rank]:
                return self._boxes[rank].pop(0)
            return None

    def wake(self, rank):
        with self._cv:
            self._cv.notify_all()


@pytest.mark.parametrize("progress", ["thread", "worker"])
def test_minimal_transport_end_to_end(progress):
    """Satellite: an end-to-end run through the base-class batching
    defaults; in worker mode there is no notify hook, so this also covers
    the timed-poll progress fallback."""
    N = 30
    got = []

    def pong(ctx, events):
        got.append(events[0].data)
        ctx.fire(0, "pong", events[0].data)

    def ping(ctx, events):
        if events[0].data < N:
            ctx.fire(1, "ping", events[0].data + 1)

    def main(ctx):
        if ctx.rank == 0:
            ctx.submit_persistent(ping, deps=[(1, "pong")])
            ctx.fire(1, "ping", 1)
        else:
            ctx.submit_persistent(pong, deps=[(0, "ping")])

    rt = edat.Runtime(2, transport=MinimalTransport(2), progress=progress,
                      unconsumed="ignore")
    rt._run_internal(main, timeout=60)
    assert got == list(range(1, N + 1))


# ------------------------------------------------- real spawned processes
def _ring_main(ctx, n_hops=100):
    left = (ctx.rank - 1) % ctx.n_ranks

    def relay(c, events):
        if events[0].data < n_hops:
            c.fire((c.rank + 1) % c.n_ranks, "token", events[0].data + 1)

    ctx.submit_persistent(relay, deps=[(left, "token")])
    if ctx.rank == 0:
        ctx.fire(1, "token", 1)


def test_launch_processes_four_rank_ring():
    stats = launch_processes(
        4, functools.partial(_ring_main, n_hops=100), timeout=60)
    assert stats["events_sent"] == stats["events_received"] == 100
    assert stats["tasks_executed"] == 100
    assert stats["run_seconds"] > 0


def test_coordinator_port_race_bind_retry(monkeypatch):
    """Regression for the _free_port TOCTOU race: the launcher probes a
    free port, releases it, and only later does the rank-0 child bind it
    as the coordinator — another process can squat it in the gap.  Here
    the test pre-occupies exactly the probed port with a listening
    socket and releases it ~1s in; the coordinator's bind-with-retry on
    EADDRINUSE must ride out the squatter instead of crashing the
    world (which is what the old single-shot bind did)."""
    from repro.net import launch as launch_mod

    squatter = socket.socket()
    squatter.bind(("127.0.0.1", 0))
    squatter.listen(1)
    port = squatter.getsockname()[1]
    monkeypatch.setattr(launch_mod, "_free_port",
                        lambda host="127.0.0.1": port)
    releaser = chaos.Saboteur(squatter.close, delay=2.5,
                              name="port-squatter").start()
    try:
        stats = launch_processes(
            2, functools.partial(_ring_main, n_hops=20), timeout=60)
    finally:
        releaser.join()
    assert stats["events_sent"] == 20            # the run really happened


def _stuck_main(ctx, ready_path=""):
    def on_fail(c, events):
        pass

    ctx.submit(on_fail, deps=[(edat.ANY, edat.RANK_FAILED)])
    if ctx.rank == 3:
        open(ready_path, "w").close()
        time.sleep(300)          # never finishes: must be SIGKILLed


def test_process_kill_detected_by_heartbeat(tmp_path):
    """Acceptance: a kill_rank-equivalent process kill is detected by the
    failure detector; survivors get RANK_FAILED and terminate cleanly."""
    ready = str(tmp_path / "ready")
    pg = ProcessGroup(4, functools.partial(_stuck_main, ready_path=ready),
                      run_timeout=60, hb_interval=0.2, hb_timeout=1.5)
    pg.start()
    chaos.sigkill_when_ready(pg, 3, ready, timeout=60, settle=0.3)
    stats = pg.wait(60)
    codes = pg.exitcodes()
    assert codes[3] != 0                      # the victim
    assert codes[0] == codes[1] == codes[2] == 0
    assert stats["tasks_executed"] == 3       # one RANK_FAILED per survivor


def _boom_main(ctx):
    def boom(c, events):
        raise ValueError("spawned-boom")

    if ctx.rank == 1:
        ctx.submit(boom)


def test_spawned_task_error_fails_every_rank():
    with pytest.raises(RuntimeError, match="spawned-boom"):
        launch_processes(2, _boom_main, timeout=30)


# ------------------------------------------------- coalescing invariants
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_coalescing_fifo_no_loss_randomized(seed):
    """Per-(src,dst) FIFO and zero event loss must hold across randomized
    batch boundaries (tiny max_batch_bytes forces frame splits; a nonzero
    flush_interval makes the writer batch aggressively) and across forced
    partial ``drain()``s on the receiver — for every encode path: deferred
    (immutable ints), snapshot (mutable dicts), and owned/zero-copy
    (numpy with ref semantics)."""
    import random
    rng = random.Random(seed)
    import numpy as np
    kw = dict(flush_interval=rng.choice([0.0, 0.001]),
              max_batch_bytes=rng.choice([128, 4096, 1 << 20]))
    ta, tb = _pair(**kw)
    N = 400
    try:
        i = 0
        while i < N:
            burst = min(rng.randrange(1, 12), N - i)
            msgs = []
            for k in range(i, i + burst):
                style = rng.randrange(3)
                if style == 0:       # deferred path (immutable payload)
                    m = _ev(0, 1, "seq", k)
                elif style == 1:     # snapshot path (mutable payload)
                    m = _ev(0, 1, "seq", {"i": k})
                else:                # owned path (zero-copy oob numpy)
                    m = _ev(0, 1, "seq", np.array([k], np.int64))
                    m.owned = True
                msgs.append(m)
            if rng.random() < 0.5:
                for m in msgs:
                    assert ta.send(m)
            else:
                assert ta.send_many(msgs) == len(msgs)
            i += burst
        got = []
        deadline = time.monotonic() + 30
        while len(got) < N and time.monotonic() < deadline:
            if rng.random() < 0.5:
                out = tb.drain(1, max_n=rng.randrange(1, 7))  # forced partial
                if not out:
                    time.sleep(0.002)
            else:
                out = tb.recv_many(1, timeout=0.2)
            for m in out:
                d = m.payload.data
                if isinstance(d, dict):
                    got.append(d["i"])
                elif isinstance(d, int):
                    got.append(d)
                else:
                    got.append(int(d[0]))
        assert got == list(range(N)), f"loss/reorder with {kw}"
        assert ta.sent_vector() == [0, N]
        assert tb.recv_vector() == [N, 0]
    finally:
        ta.close()
        tb.close()


def test_coalescing_snapshot_at_fire_mutable_payload():
    """A mutable payload mutated right after send() must arrive with its
    fire-time value: the coalescing layer snapshots (pickles) non-owned
    payloads synchronously inside send, not in the writer thread."""
    import numpy as np
    ta, tb = _pair(flush_interval=0.05)  # writer waits: mutation races it
    try:
        buf = np.array([1, 2, 3])
        assert ta.send(_ev(0, 1, "snap", {"buf": buf}))
        buf[:] = 99  # post-fire mutation must not be observable
        deadline = time.monotonic() + 10
        got = []
        while not got and time.monotonic() < deadline:
            got = tb.recv_many(1, timeout=0.5)
        assert list(got[0].payload.data["buf"]) == [1, 2, 3]
    finally:
        ta.close()
        tb.close()


def test_coalescing_owned_numpy_arrives_writable():
    """Owned (ref) numpy payloads travel zero-copy and must reconstruct
    as writable arrays on the receiving side."""
    import numpy as np
    ta, tb = _pair()
    try:
        m = _ev(0, 1, "z", np.arange(1000, dtype=np.float32))
        m.owned = True
        assert ta.send(m)
        deadline = time.monotonic() + 10
        got = []
        while not got and time.monotonic() < deadline:
            got = tb.recv_many(1, timeout=0.5)
        arr = got[0].payload.data
        np.testing.assert_array_equal(arr,
                                      np.arange(1000, dtype=np.float32))
        arr[:] = 0.0  # raises if the zero-copy view came back read-only
    finally:
        ta.close()
        tb.close()


def test_transport_flush_drains_queue():
    ta, tb = _pair(flush_interval=0.02)
    try:
        ta.send_many([_ev(0, 1, "f", i) for i in range(50)])
        assert ta.flush(timeout=10.0)
        deadline = time.monotonic() + 5
        got = []
        while len(got) < 50 and time.monotonic() < deadline:
            got += tb.recv_many(1, timeout=0.5)
        assert len(got) == 50
    finally:
        ta.close()
        tb.close()


@pytest.mark.parametrize("progress", ["thread", "worker"])
def test_distributed_coalesced_stream_both_modes(progress):
    """End-to-end dual-Runtime run over the coalescing transport in both
    progress modes: a mixed stream (fire + fire_batch, plain + ref numpy
    payloads) keeps FIFO order and loses nothing."""
    import numpy as np
    N = 60
    got = []

    def sink(ctx, events):
        d = events[0].data
        got.append(int(d["i"]) if isinstance(d, dict) else int(d[0]))

    def main(ctx):
        if ctx.rank == 0:
            ctx.submit_persistent(sink, deps=[(1, "s")])
        else:
            i = 0
            while i < N:
                if i % 3 == 0:
                    ctx.fire(0, "s", np.array([i], np.int64), ref=True)
                    i += 1
                else:
                    n = min(3, N - i)
                    ctx.fire_batch([(0, "s", {"i": i + k})
                                    for k in range(n)])
                    i += n

    res = _dual_runtime_run(main, progress=progress)
    assert [r[0] for r in res] == ["ok", "ok"]
    assert got == list(range(N))


# --------------------------------- drop accounting when a peer dies mid-run
def test_drop_queue_exactly_once_under_enqueue_race():
    """Events parked on a dead peer's coalescing queue are counted dropped
    exactly once — whether the death verdict drained them, or the enqueue
    lost the race and observed the queue's dead flag."""
    ta, tb = _pair(coalesce=True, flush_interval=5.0)
    try:
        for i in range(5):
            assert ta.send(_ev(0, 1, "q", i))
        # all 5 sit unwritten (the writer waits out flush_interval)
        ta._declare_proc_dead(1)
        assert ta.dropped == 5                  # drained queue, counted once
        # an enqueue that lost the race against the verdict accounts its
        # own items instead of parking them on the dead queue
        ta._enqueue(1, [_ev(0, 1, "q", 99)])
        assert ta.dropped == 6
        t0 = time.monotonic()
        assert ta.flush(timeout=5.0) is True    # nothing left to drain
        assert time.monotonic() - t0 < 1.0
    finally:
        ta.close()
        tb.close()


def test_flush_unblocks_when_peer_dies_mid_drain():
    ta, tb = _pair(coalesce=True, flush_interval=5.0)
    try:
        for i in range(3):
            assert ta.send(_ev(0, 1, "q", i))
        res = {}

        def fl():
            res["ok"] = ta.flush(timeout=10.0)

        th = threading.Thread(target=fl)
        th.start()
        time.sleep(0.2)                 # flush is now waiting on the queue
        ta._declare_proc_dead(1)
        th.join(3.0)
        assert not th.is_alive(), "flush hung on a dead peer's queue"
        assert ta.dropped == 3          # the waited-on events were counted
    finally:
        ta.close()
        tb.close()


def _flood_main(ctx, ready_path=""):
    if ctx.rank == 0:
        def sink(c, events):
            if not os.path.exists(ready_path):
                open(ready_path, "w").close()
        ctx.submit_persistent(sink, deps=[(1, "flood")])
        ctx.submit(lambda c, e: None, deps=[(edat.ANY, edat.RANK_FAILED)])
    else:
        payload = b"x" * 512
        for _ in range(20000):
            ctx.fire(0, "flood", payload)


def test_kill_mid_flood_terminates_with_balanced_drops(tmp_path):
    """Chaos: SIGKILL the producer while its coalescing queue is loaded.
    The round must still reach global termination well inside the run
    deadline — which it only can if every in-flight event was counted
    either received or dropped (the Mattern condition), i.e. nothing was
    double-counted or lost by the queue-drop path."""
    from repro import edat as _edat
    ready = str(tmp_path / "ready")
    with _edat.Session(2, transport="socket", timeout=120,
                       hb_interval=0.2, hb_timeout=1.5, unconsumed="ignore",
                       flush_interval=0.005, max_batch_bytes=32768) as s:
        s.start(functools.partial(_flood_main, ready_path=ready))
        deadline = time.monotonic() + 60
        while not os.path.exists(ready) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert os.path.exists(ready), "flood never reached the sink"
        s.kill(1)
        t0 = time.monotonic()
        stats = s.wait(timeout=60, check=False)
        assert time.monotonic() - t0 < 45      # terminated, not timed out
        assert s.exitcodes()[1] not in (None, 0)
        assert stats.get("events_received", 0) > 0
