"""The one traffic generator. A traffic mix is a JSON file of parameters
under ``bench/traffic/``; this module turns it and a seed into requests.

Two shapes of traffic, chosen by the file's ``driver``:

* ``search`` — a request stream in scheduler-iteration units, the input of
  a mapping search: log-normal (prompt, output) lengths clipped to
  ``[min_len, max_len]``, Poisson arrivals at ``rate``
  requests per iteration, and a ``warm_fraction`` of requests that arrive
  decode-resident at a random point of their output. The stream is drawn
  from ``stream_seed`` (fixed in the file), so every run searches the same
  scenario; the run's seed drives the search itself.
* ``serve`` — timed requests for the served path: a pool of (prompt,
  output) pairs drawn once from ``pool_seed``, clipped to ``max_prompt``
  and to ``max_len`` in all. ``arrival`` ``backlog``: ``n_requests`` pairs
  all due at once, a queue deeper than the window can drain.
  ``poisson``: ``round(rate_per_s * span)`` pairs and as many Poisson gaps
  from the pool, scaled so the arrivals span exactly ``warmup_s +
  seconds``. The run's seed permutes the pairs and the gaps and draws the
  prompt tokens, so every seed offers the same work in another order.
"""
from __future__ import annotations

import math

import numpy as np


def lognormal_lengths(rng, mean: float, sigma: float, n: int, lo: int,
                      hi: int) -> np.ndarray:
    """Log-normal lengths with the given mean, rounded and clipped."""
    mu = math.log(mean) - sigma ** 2 / 2.0
    x = rng.lognormal(mu, sigma, size=n)
    return np.clip(np.round(x), lo, hi).astype(int)


def search_stream(p: dict) -> list:
    """[(prompt_len, max_new_tokens, arrival_iter, warm_context)] of a
    search mix. Each field has its own child generator, so the lengths do
    not depend on the arrival process."""
    n = int(p["n_requests"])
    ln = p["lengths"]
    len_rng, gap_rng, warm_rng, ctx_rng = (
        np.random.default_rng(c)
        for c in np.random.SeedSequence(int(p["stream_seed"])).spawn(4))
    ins = lognormal_lengths(len_rng, ln["mean_input"], ln["sigma_input"], n,
                            ln["min_len"], ln["max_len"])
    outs = lognormal_lengths(len_rng, ln["mean_output"], ln["sigma_output"],
                             n, ln["min_len"], ln["max_len"])
    if p["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {p['arrival']!r}")
    gaps = gap_rng.exponential(1.0 / p["rate"], size=n)
    arrivals = np.floor(np.cumsum(gaps) - gaps[0]).astype(int)
    warm = warm_rng.random(n) < p["warm_fraction"]
    ctx_u = ctx_rng.random(n)
    cap = p.get("max_new_tokens_cap")
    out = []
    for i in range(n):
        new = max(int(outs[i]) if cap is None else min(int(outs[i]), cap), 1)
        ctx = int(ins[i] + ctx_u[i] * outs[i]) + 1 if warm[i] else 0
        out.append((int(ins[i]), new, int(arrivals[i]), ctx))
    return out


def serve_requests(p: dict, seed: int, seconds: float, vocab: int,
                   rate_per_s: float | None = None) -> list:
    """[(due_s, prompt_tokens, max_new_tokens)] sorted by due time; see the
    module docstring. ``rate_per_s`` makes the arrivals Poisson at that
    rate whatever the file says (sweeps)."""
    poisson = rate_per_s is not None or p["arrival"] == "poisson"
    span = float(p["warmup_s"]) + float(seconds)
    if poisson:
        rate = float(p["rate_per_s"] if rate_per_s is None else rate_per_s)
        n = max(2, int(round(rate * span)))
    elif p["arrival"] == "backlog":
        n = int(p["n_requests"])
    else:
        raise ValueError(f"unknown arrival process {p['arrival']!r}")
    ln = p["lengths"]
    pool = np.random.default_rng(int(p["pool_seed"]))
    ins = lognormal_lengths(pool, ln["mean_input"], ln["sigma_input"], n,
                            ln["min_len"], p["max_prompt"])
    outs = lognormal_lengths(pool, ln["mean_output"], ln["sigma_output"], n,
                             ln["min_len"], p["max_len"])
    outs = np.minimum(outs, p["max_len"] - ins)
    gaps = pool.exponential(1.0, size=n)
    gaps *= span / gaps.sum() if poisson else 0.0
    run = np.random.default_rng(np.random.SeedSequence(int(seed)))
    order = run.permutation(n)
    gaps = gaps[run.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    out = []
    for i, j in enumerate(order):
        prompt = run.integers(0, vocab, size=int(ins[j])).tolist()
        out.append((float(due[i]), prompt, int(outs[j])))
    return out
