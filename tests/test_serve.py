"""Acceptance for ``repro.serve``: event-driven LM serving.

* loadgen units: deterministic schedules, unique ids, honest summaries;
* engine units: the two bugfixes at the KV-cache layer — a dead slot's
  position stays pinned, and ``attach`` fully overwrites a reused slot;
* regression (duplicate decode chains): a 2-client burst must satisfy
  ``tick_execs == engine steps`` *exactly*.  Pre-fix code fired a new
  self-sustaining ``decode_tick`` chain per admission; the extra chains
  surface as tick executions that find no live slot and step nothing,
  breaking the equality;
* regression (stale KV on slot reuse): with fewer slots than requests,
  every served token stream must match a fresh sequential server
  token-for-token.  Pre-fix code spliced nothing on admit (a reused slot
  decoded against its previous occupant's attention state) and advanced
  dead slots' positions unboundedly;
* parity matrix: the same load through ``Session(ranks=3)`` on inproc
  and socket/2-procs produces the sequential baseline's exact greedy
  tokens;
* live backpressure: an offered rate the slots cannot sustain trips the
  event-carried ``backpressure`` channel and the
  ``admission-backpressure`` insights rule;
* chaos: SIGKILL one client mid-load — the server purges the dead
  client's queue, drains its live slots, and the round terminates
  cleanly with no leaked slots.
"""
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import _chaos as chaos                                    # noqa: E402

from repro import edat                                    # noqa: E402
from repro.configs import ARCHS, reduce_cfg               # noqa: E402
from repro.serve import (DEFAULT_MAX_LEN, LoadSpec,       # noqa: E402
                         SequentialEngine, ServeEngine, all_requests,
                         client_schedule, percentile, run_sequential,
                         run_serve, serve_program, summarize)

pytestmark = pytest.mark.timeout(600)

ARCH = "gemma3-1b"
MAX_LEN = 48


@pytest.fixture(scope="module")
def cfg():
    return reduce_cfg(ARCHS[ARCH].cfg)


# ---------------------------------------------------------------- loadgen
def test_schedule_deterministic_unique_sorted():
    spec = LoadSpec(rps=10, requests=13, seed=3)
    a = client_schedule(spec, 0, 3, vocab=512)
    b = client_schedule(spec, 0, 3, vocab=512)
    assert a == b                               # regenerable exactly
    assert spec.split(3) == [5, 4, 4]
    merged = all_requests(spec, 3, vocab=512)
    assert len(merged) == 13
    assert len({r["id"] for r in merged}) == 13
    assert [r["t"] for r in merged] == sorted(r["t"] for r in merged)
    for r in merged:
        assert len(r["prompt"]) in spec.prompt_lens
        assert spec.max_new_lo <= r["max_new"] <= spec.max_new_hi
        assert all(0 <= t < 512 for t in r["prompt"])


def test_clients_draw_different_streams():
    spec = LoadSpec(rps=10, requests=8, seed=0)
    a = client_schedule(spec, 0, 2, vocab=512)
    b = client_schedule(spec, 1, 2, vocab=512)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_percentile_nearest_rank():
    assert percentile([], 99) == 0.0
    xs = list(range(1, 101))
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 100) == 100.0
    assert percentile(xs, 50) == 51.0           # nearest-rank on 0..99


def test_summarize_measures_from_schedule_time():
    recs = [{"t_sched": 0.0, "t_first": 0.5, "t_done": 1.5, "n_out": 11}]
    s = summarize(recs, 2.0)
    assert s["requests"] == 1 and s["tokens"] == 11
    assert s["ttft_p50_ms"] == pytest.approx(500.0)
    assert s["per_token_p50_ms"] == pytest.approx(100.0)
    assert s["tokens_per_s"] == pytest.approx(5.5)


# ----------------------------------------------------------------- engine
def test_engine_dead_slot_pos_pinned(cfg):
    """The unbounded-position bug: stepping the batch must not advance a
    slot that has no live request, or an idle slot walks its cache write
    pointer to max_len and corrupts the next occupant."""
    eng = ServeEngine(cfg, slots=2, max_len=MAX_LEN)
    prompt = list(range(1, 9))
    first, pc = eng.prefill(prompt)
    eng.attach(0, len(prompt), first, pc)
    assert int(eng.pos[1, 0]) == 0
    for _ in range(5):
        eng.step([0])
    assert int(eng.pos[0, 0]) == len(prompt) + 5
    assert int(eng.pos[1, 0]) == 0              # dead slot pinned


def test_engine_slot_reuse_matches_fresh(cfg):
    """The stale-KV bug: serve request A in slot 0, then admit B into the
    same slot — B's tokens must equal a fresh engine's, i.e. ``attach``
    really resets every cache leaf of the slot."""
    eng = ServeEngine(cfg, slots=1, max_len=MAX_LEN)
    rng = np.random.default_rng(7)

    def serve(e, prompt, n):
        first, pc = e.prefill(prompt)
        e.attach(0, len(prompt), first, pc)
        out = [first]
        for _ in range(n - 1):
            out.append(int(e.step([0])[0]))
        return out

    pa = rng.integers(0, cfg.vocab, size=8).tolist()
    pb = rng.integers(0, cfg.vocab, size=12).tolist()
    serve(eng, pa, 10)                          # occupy + dirty slot 0
    reused = serve(eng, pb, 10)                 # reuse the slot
    fresh = serve(ServeEngine(cfg, slots=1, max_len=MAX_LEN), pb, 10)
    assert reused == fresh


# ----------------------------------------------------- program regressions
def test_single_decode_chain_under_burst():
    """Duplicate-chain regression: every ``decode_tick`` execution must
    step the batch (``tick_execs == steps`` exactly).  Without the
    ``_ticking`` guard each admission starts another chain; once the
    batch drains, the surplus chains' ticks execute against an empty
    batch and the equality breaks."""
    load = LoadSpec(rps=1000.0, requests=8, prompt_lens=(4, 8),
                    max_new_lo=4, max_new_hi=8, seed=1)
    out = run_serve(arch=ARCH, clients=2, slots=4, max_len=MAX_LEN,
                    load=load, transport="inproc")
    res = out["result"]
    assert res["served"] == 8
    assert res["slots_leaked"] == 0 and res["queue_left"] == 0
    assert res["tick_execs"] == res["steps"], (
        "extra no-op decode_tick executions: more than one chain ran")
    # 8 requests of <= 8 tokens through 4 slots: if every tick does
    # batch work, far fewer ticks than serving one token per tick
    assert res["steps"] <= 2 * 8 * 8


def _seq_tokens(cfg, load, clients):
    reqs = all_requests(load, clients, cfg.vocab)
    recs = run_sequential(cfg, reqs, max_len=MAX_LEN, realtime=False)
    return {r["id"]: r["tokens"] for r in recs}


@pytest.mark.parametrize("transport,procs", [("inproc", None),
                                             ("socket", 2)])
def test_tokens_match_sequential_baseline(cfg, transport, procs):
    """Parity matrix (stale-KV regression at the session level): 2 slots
    for 7 requests forces slot reuse; every response must carry exactly
    the greedy tokens a fresh one-at-a-time server produces, on both
    transports."""
    load = LoadSpec(rps=50.0, requests=7, prompt_lens=(4, 8, 12),
                    max_new_lo=3, max_new_hi=8, seed=2)
    out = run_serve(arch=ARCH, clients=2, slots=2, max_len=MAX_LEN,
                    load=load, transport=transport, procs=procs)
    res = out["result"]
    assert res["served"] == 7 and res["slots_leaked"] == 0
    got = {r["id"]: r["tokens"] for r in res["records"]}
    assert got == _seq_tokens(cfg, load, 2)


def test_backpressure_throttles_and_insights_flag_it():
    """One slot against an offered rate it cannot sustain (long outputs,
    arrivals faster than drains): the admission queue must cross its
    bound, fire ``backpressure`` to the clients — who must measurably
    gate their schedule on it — and the run's own counters must trip the
    ``admission-backpressure`` insights rule."""
    from repro.insights import analyze
    load = LoadSpec(rps=20.0, requests=16, prompt_lens=(4,),
                    max_new_lo=24, max_new_hi=32, seed=0)
    out = run_serve(arch=ARCH, clients=2, slots=1, max_len=MAX_LEN,
                    load=load, queue_bound=2, transport="inproc")
    res = out["result"]
    assert res["served"] == 16 and res["slots_leaked"] == 0
    assert res["bp_signals"] >= 1
    throttled = sum(r["throttled_s"] for r in res["records"])
    assert throttled > 0                 # clients genuinely gated
    rules = [f.rule for f in analyze(out["stats"])]
    assert "admission-backpressure" in rules


def test_traced_serving_records_request_and_step_spans():
    """``Session(trace=True)``: every request has its ``serve.request``,
    ``serve.prefill`` and ``serve.attach`` spans under one ``req``, each
    under its task; one ``serve.step`` per engine step, each holding
    ``engine.step`` with its launch and read."""
    load = LoadSpec(rps=200.0, requests=6, prompt_lens=(4, 8),
                    max_new_lo=3, max_new_hi=6, seed=4)
    with edat.Session(3, workers_per_rank=2, unconsumed="ignore",
                      trace=True, timeout=300) as s:
        s.run(serve_program(arch=ARCH, slots=2, max_len=MAX_LEN,
                            load=load))
        res = s.gather()
        ranks = s.stats()["ranks"]
    assert all(rk["trace_dropped"] == 0 for rk in ranks.values())
    spans = [rec for rk in ranks.values() for rec in rk["trace"]]
    by_id = {rec[3]: rec for rec in spans}
    ids = sorted(r["id"] for r in res["records"])
    assert res["served"] == 6
    for name, task in (("serve.request", "serve.request"),
                       ("serve.prefill", "serve.prefill"),
                       ("serve.attach", "serve.prefill")):
        got = [rec for rec in spans if rec[0] == name]
        assert sorted(rec[5]["req"] for rec in got) == ids
        for rec in got:
            parent = by_id[rec[4]]
            assert parent[0] == "edat.task" and parent[5]["task"] == task
    steps = [rec for rec in spans if rec[0] == "serve.step"]
    assert len(steps) == res["steps"] > 0
    for st in steps:
        kids = [rec for rec in spans if rec[4] == st[3]]
        assert [k[0] for k in kids] == ["engine.step"]
        assert sorted(rec[0] for rec in spans if rec[4] == kids[0][3]) == [
            "engine.step.launch", "engine.step.read"]
    # each token after a request's first comes from a step it was live in
    assert sum(len(st[5]["live"]) for st in steps) == sum(
        r["n_out"] - 1 for r in res["records"])


# ------------------------------------------------------------------- chaos
def test_client_sigkill_drains_cleanly(tmp_path):
    """SIGKILL one of two client processes once the server has admitted
    its first request.  The server's RANK_FAILED task purges the dead
    client's queue; its live slots drain; the survivor's whole schedule
    is served; the round terminates with no leaked slots."""
    ready = str(tmp_path / "ready")
    load = LoadSpec(rps=10.0, requests=12, prompt_lens=(4, 8),
                    max_new_lo=4, max_new_hi=8, seed=4)
    with edat.Session(3, procs=3, transport="socket", timeout=300,
                      workers_per_rank=2, unconsumed="ignore",
                      hb_interval=0.2, hb_timeout=1.5) as s:
        s.start(edat.deferred(serve_program, arch=ARCH, slots=2,
                              max_len=MAX_LEN, load=load,
                              ready_file=ready, ready_after=1))
        chaos.sigkill_when_ready(s, 2, ready, timeout=120, settle=0.2)
        s.wait(240, check=False)
        codes = s.exitcodes()
        res = s.gather()
    assert codes[2] not in (None, 0)            # the victim died by kill
    assert codes[0] == 0 and codes[1] == 0      # server + survivor: clean
    assert res["dead"] == [2]
    assert res["slots_leaked"] == 0 and res["queue_left"] == 0
    # the surviving client (rank 1 == loadgen client 0) got everything
    cfg = reduce_cfg(ARCHS[ARCH].cfg)
    survivor_ids = {r["id"] for r in client_schedule(load, 0, 2,
                                                     cfg.vocab)}
    served_ids = {r["id"] for r in res["records"]}
    assert survivor_ids <= served_ids


def test_server_sigkill_clients_surface_rankdied(tmp_path):
    """SIGKILL the *server* process (rank 0 — also the termination
    coordinator) once it has admitted its first request.  The clients
    cannot finish — nobody will ever broadcast terminate — but they must
    not hang either: each client runtime raises ``RankDiedError`` naming
    rank 0, and the launcher treats that as an orderly child outcome
    (a ``rankdied`` report, exit code 0)."""
    ready = str(tmp_path / "ready")
    load = LoadSpec(rps=10.0, requests=12, prompt_lens=(4, 8),
                    max_new_lo=4, max_new_hi=8, seed=4)
    with edat.Session(3, procs=3, transport="socket", timeout=300,
                      workers_per_rank=2, unconsumed="ignore",
                      hb_interval=0.2, hb_timeout=1.5) as s:
        s.start(edat.deferred(serve_program, arch=ARCH, slots=2,
                              max_len=MAX_LEN, load=load,
                              ready_file=ready, ready_after=1))
        chaos.sigkill_when_ready(s, 0, ready, timeout=120, settle=0.2)
        s.wait(240, check=False)
        codes = s.exitcodes()
        res = s.gather()
        reports = s._last_pg.child_reports
    assert codes[0] not in (None, 0)            # the server died by kill
    assert codes[1] == 0 and codes[2] == 0      # clients: orderly exit
    assert res is None                          # rank 0 never finalized
    died = sorted(r for r in reports if r[0] == "rankdied")
    assert [r[1] for r in died] == [1, 2]       # both clients reported
    for r in died:
        assert "rank 0" in r[2] and "termination coordinator" in r[2]
