"""One generator for every traffic mix in ``traffic/<mix>.json``.

Arrival processes (``arrival``):

* ``poisson`` — open loop at ``rate_rps`` requests a second.
* ``bursty`` — open loop, a two-state modulated Poisson process: the rate
  is ``burst_factor`` times the base rate for the first ``burst_s``
  seconds of every ``period_s``, the base rate otherwise, and
  ``rate_rps`` is the mean over a period.
* ``saturate`` — Poisson gaps at ``rate_rps``, well above what the
  server sustains, each counted from the client's previous send: the
  server's backpressure holds the clients back, and they stop when the
  window closes (requests not sent by then are not attempted).

Every seed gets the same work in the same order: the request count, the
prompt lengths (bucket counts by the weights, largest remainder), the
output lengths (evenly spread over ``max_new``) and the gaps between
arrivals (the quantiles of the unit exponential, mapped through the
integrated rate) are fixed by the mix and the window, and shuffled once,
by stream 0; the seed draws only the prompt tokens.  Where the queue at
a fixed rate swings with the order of arrivals, this keeps the tails a
measurement of the server.  The open-loop request list follows
``repro.serve.loadgen.client_schedule``'s shape (``id``, ``t``,
``prompt``, ``max_new``); request ``i`` of the merged schedule goes to
client ``i % clients``.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ARRIVALS = ("poisson", "bursty", "saturate")


def load(name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("arrival") not in ARRIVALS:
        raise ValueError(f"traffic {name}: arrival must be one of "
                         f"{ARRIVALS}, not {mix.get('arrival')!r}")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent stream ``stream`` of a seed of any size or sign."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def _rate_breakpoints(mix: Dict[str, Any], seconds: float):
    """Times and integrated rate at the breakpoints of the piecewise
    constant arrival rate on ``[0, seconds]``."""
    if mix["arrival"] in ("poisson", "saturate"):
        return (np.array([0.0, seconds]),
                np.array([0.0, mix["rate_rps"] * seconds]))
    factor, burst, period = (float(mix["burst_factor"]),
                             float(mix["burst_s"]), float(mix["period_s"]))
    base = mix["rate_rps"] * period / (factor * burst + period - burst)
    ts = sorted({0.0, seconds}
                | {t for k in range(int(seconds // period) + 1)
                   for t in (k * period, k * period + burst) if t < seconds})
    ts = np.array(ts)
    lam = np.zeros_like(ts)
    for i in range(1, len(ts)):
        mid = 0.5 * (ts[i - 1] + ts[i])
        rate = base * factor if (mid % period) < burst else base
        lam[i] = lam[i - 1] + rate * (ts[i] - ts[i - 1])
    return ts, lam


def arrival_times(mix: Dict[str, Any], seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival offsets in ``(0, seconds]``."""
    ts, lam = _rate_breakpoints(mix, seconds)
    n = int(round(lam[-1]))
    if n < 1:
        raise ValueError(f"mix offers no request in {seconds} s")
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    u = np.cumsum(gaps) * (lam[-1] / gaps.sum())
    return np.interp(u, lam, ts)


def _bucket_counts(weights: List[float], n: int) -> List[int]:
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def schedule(mix: Dict[str, Any], seconds: float, seed: int,
             vocab: int) -> List[Dict[str, Any]]:
    """The merged request list ``[{id, client, t, prompt, max_new}]``,
    sorted by arrival offset ``t`` (seconds from the window's start)."""
    rng = rng_for(0, 1)   # one order of the work, the same for every seed
    tok = rng_for(seed, 3)
    times = arrival_times(mix, seconds, rng)
    n = len(times)
    lens = mix["prompt_lens"]
    plens = np.repeat(lens, _bucket_counts(mix["prompt_weights"], n))
    plens = rng.permutation(plens)
    lo, hi = mix["max_new"]
    outs = lo + np.floor((np.arange(n) + 0.5) * (hi - lo + 1) / n)
    outs = rng.permutation(outs.astype(int))
    clients = int(mix["clients"])
    return [{"id": i, "client": i % clients, "t": float(times[i]),
             "prompt": tok.integers(0, vocab, size=int(plens[i])).tolist(),
             "max_new": int(outs[i])}
            for i in range(n)]


def by_client(reqs: List[Dict[str, Any]],
              clients: int) -> List[List[Dict[str, Any]]]:
    out: List[List[Dict[str, Any]]] = [[] for _ in range(clients)]
    for r in reqs:
        out[r["client"]].append(r)
    return out
