"""The traffic generator: deterministic per seed, the same work for every
seed, and what each mix file says."""
from __future__ import annotations

import glob
import os
from collections import Counter

import numpy as np
import pytest

import _chipbench  # noqa: F401
from chip import traffic

MIXES = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(_chipbench.ROOT, "benchmarks", "chip", "traffic",
                 "*.json")))
SECONDS = 30.0
VOCAB = 1000


@pytest.mark.parametrize("mix", MIXES)
def test_deterministic_per_seed(mix):
    m = traffic.load(mix)
    a = traffic.schedule(m, SECONDS, 2**40 + 7, VOCAB)
    b = traffic.schedule(m, SECONDS, 2**40 + 7, VOCAB)
    c = traffic.schedule(m, SECONDS, 7, VOCAB)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


@pytest.mark.parametrize("mix", MIXES)
def test_same_work_for_every_seed(mix):
    """Two seeds get the same arrivals, prompt lengths and output lengths
    in the same order, and differ only in the prompt tokens."""
    m = traffic.load(mix)
    s1 = traffic.schedule(m, SECONDS, 1, VOCAB)
    s2 = traffic.schedule(m, SECONDS, 3_000_000_017, VOCAB)
    strip = [[(r["t"], len(r["prompt"]), r["max_new"]) for r in s]
             for s in (s1, s2)]
    assert strip[0] == strip[1]
    assert [r["prompt"] for r in s1] != [r["prompt"] for r in s2]
    # in the time of the integrated rate (unit-rate arrivals) the gaps
    # are the quantiles of the unit exponential, in some order
    bt, blam = traffic._rate_breakpoints(m, SECONDS)
    gaps = np.diff([0.0] + list(np.interp([r["t"] for r in s1], bt, blam)))
    n = len(gaps)
    want = -np.log1p(-(np.arange(n) + 0.5) / n)
    want *= blam[-1] / want.sum()
    np.testing.assert_allclose(np.sort(gaps), want, rtol=1e-6, atol=1e-9)
    assert not np.allclose(gaps, np.sort(gaps))


@pytest.mark.parametrize("mix", MIXES)
def test_matches_its_file(mix):
    m = traffic.load(mix)
    reqs = traffic.schedule(m, SECONDS, 11, VOCAB)
    n = len(reqs)
    lens = Counter(len(r["prompt"]) for r in reqs)
    assert set(lens) <= set(m["prompt_lens"])
    w = np.asarray(m["prompt_weights"], float)
    for plen, wi in zip(m["prompt_lens"], w / w.sum()):
        assert abs(lens[plen] - n * wi) <= 1
    lo, hi = m["max_new"]
    outs = [r["max_new"] for r in reqs]
    assert min(outs) >= lo and max(outs) <= hi
    assert abs(np.mean(outs) - (lo + hi) / 2) <= 0.5 + (hi - lo) / n
    assert all(0 <= t < VOCAB for r in reqs for t in r["prompt"])
    ids = [r["id"] for r in reqs]
    assert ids == list(range(n))
    per_client = traffic.by_client(reqs, m["clients"])
    assert sum(map(len, per_client)) == n
    assert all(r["client"] == c for c, rs in enumerate(per_client)
               for r in rs)
    ts = [r["t"] for r in reqs]
    assert ts == sorted(ts)
    assert 0 < ts[0] and ts[-1] <= SECONDS + 1e-9
    assert n == round(m["rate_rps"] * SECONDS)


def test_burst_shape():
    m = {"arrival": "bursty", "rate_rps": 40.0, "burst_factor": 4.0,
         "burst_s": 1.0, "period_s": 5.0, "clients": 2,
         "prompt_lens": [8], "prompt_weights": [1], "max_new": [1, 2]}
    # every seed gets one order of the gaps: a long window keeps the share
    # of them that falls into bursts near its expectation
    seconds = 500.0
    ts = np.array([r["t"] for r in traffic.schedule(m, seconds, 5, 10)])
    base = 40.0 * 5.0 / (4.0 * 1.0 + 4.0)
    in_burst = (ts % 5.0) < 1.0
    burst_rate = in_burst.sum() / (seconds / 5.0 * 1.0)
    calm_rate = (~in_burst).sum() / (seconds / 5.0 * 4.0)
    assert burst_rate == pytest.approx(4.0 * base, rel=0.02)
    assert calm_rate == pytest.approx(base, rel=0.02)
    assert len(ts) == round(40.0 * seconds)


def test_poisson_gaps_are_exponential_quantiles():
    m = {"arrival": "poisson", "rate_rps": 10.0, "clients": 1,
         "prompt_lens": [4], "prompt_weights": [1], "max_new": [1, 1]}
    ts = [r["t"] for r in traffic.schedule(m, 40.0, 3, 10)]
    gaps = np.sort(np.diff([0.0] + ts))
    n = len(gaps)
    want = -np.log1p(-(np.arange(n) + 0.5) / n)
    want *= 40.0 / want.sum()
    np.testing.assert_allclose(gaps, want, rtol=1e-6)


def test_unknown_arrival_refused(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "x.json").write_text('{"arrival": "zipf"}')
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="arrival"):
        traffic.load("x")
