#!/usr/bin/env python3
"""Find the knee of a serving cell once, by a sweep of offered rates.

    python3 bench/sweep.py --workload serve.qwen1.5-0.5b.sharegpt-backlog \\
        --rates 0.25,0.5,1,1.5,2,3 --seconds 20

First an unloaded run: three requests of the mix, 100 output tokens at
most, due 6 s apart, so each is served alone. Its TTFT and TPOT (90th
percentiles) times ``--ttft-x`` and ``--tpot-x`` are the two limits. Then
for each rate one open-loop serve of the cell's mix (the traffic file's
warm-up, then ``--seconds`` of window) reports, over the requests due in
the window, the share that met both limits, the tails, the tokens per
second, and how late the service released the window's requests after
their due times (more than a second: a backlog). The knee is the highest
rate at which at least 90 % of the requests met both limits with no
backlog. The cell runs at 0.8 times it; the traffic file holds that rate
as a number, written by hand from this sweep.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, serve, traffic  # noqa: E402


def served_window(res, reqs, due_abs, wall_t0, lo, hi) -> dict:
    """Per-request latencies of the requests due in [lo, hi) of a serve
    that ran to its end (the sweep's open-loop runs)."""
    idx = [i for i, r in enumerate(reqs) if lo <= due_abs[r.rid] < hi]
    ttft, tpot, pairs, failed = [], [], [], 0
    late = 0.0
    for i in idx:
        r = reqs[i]
        ev = res.wall_events.get(r.rid, {})
        if "arrival_s" in ev:
            late = max(late, wall_t0 + ev["arrival_s"] - due_abs[r.rid])
        if "first_s" not in ev:
            failed += 1
            continue
        first = wall_t0 + ev["first_s"]
        ttft.append(first - due_abs[r.rid])
        gap = 0.0                       # one token: no gap to keep
        if "done_s" in ev and len(r.generated) >= 2:
            gap = (wall_t0 + ev["done_s"] - first) / (len(r.generated) - 1)
            tpot.append(gap)
        elif "done_s" not in ev:
            failed += 1
            gap = float("inf")
        pairs.append((ttft[-1], gap))
    return {"idx": idx, "ttft": np.asarray(ttft), "tpot": np.asarray(tpot),
            "pairs": pairs, "failed": failed, "late_s": late}


def p90(x) -> float:
    return float(np.percentile(x, 90)) if len(x) else float("inf")


def one(ctx, cfg, params, mix, seconds):
    spans = harness.Spans(False)
    svc, res, reqs, probe, due_abs, t_open, t_close = serve.serve_once(
        ctx, cfg, params, mix, seconds, spans, stop_at_close=False)
    wall_t0 = svc._wall_t0
    probe.detach()
    del svc
    gc.collect()
    win = served_window(res, reqs, due_abs, wall_t0, t_open, t_close)
    out_tok = sum(1 for p in probe.prefills if p[4] and t_open <= p[1] < t_close)
    out_tok += sum(d[2] for d in probe.decodes if t_open <= d[1] < t_close)
    return win, out_tok / seconds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--ttft-x", type=float, default=10.0)
    ap.add_argument("--tpot-x", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    harness.setup_process(cell)

    from bench.weights import make_params

    cfg, tr = cell.config, cell.traffic
    ctx = harness.RunContext(cell, args.seed, args.seconds, False,
                             time.perf_counter())
    params = make_params(cfg["model"], harness.seed_child(args.seed, 1))
    vocab = cfg["model"]["vocab"]

    base = traffic.serve_requests(tr, args.seed, args.seconds, vocab,
                                  rate_per_s=1.0)[:3]
    alone = [(6.0 * i, p, min(n, 100)) for i, (_, p, n) in enumerate(base)]
    warm = tr["warmup_s"]
    tr["warmup_s"] = 0.0
    win, _ = one(ctx, cfg, params, alone, 18.0)
    tr["warmup_s"] = warm
    ttft0, tpot0 = p90(win["ttft"]), p90(win["tpot"])
    lim_ttft, lim_tpot = args.ttft_x * ttft0, args.tpot_x * tpot0
    print(json.dumps({"unloaded_ttft_p90_ms": 1e3 * ttft0,
                      "unloaded_tpot_p90_ms": 1e3 * tpot0,
                      "ttft_limit_ms": 1e3 * lim_ttft,
                      "tpot_limit_ms": 1e3 * lim_tpot}), flush=True)
    knee = None
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = traffic.serve_requests(tr, args.seed, args.seconds, vocab,
                                     rate_per_s=rate)
        win, tps = one(ctx, cfg, params, mix, args.seconds)
        n = len(win["idx"])
        tt = list(win["ttft"])
        ok = sum(1 for a, b in win["pairs"] if a <= lim_ttft and b <= lim_tpot)
        share = ok / n if n else 0.0
        grows = win["late_s"] > 1.0     # arrivals held back: a backlog
        row = {"rate_per_s": rate, "requests": n, "met_both": share,
               "ttft_p50_ms": 1e3 * float(np.median(tt)) if tt else None,
               "ttft_p90_ms": 1e3 * p90(win["ttft"]),
               "tpot_p90_ms": 1e3 * p90(win["tpot"]),
               "output_tokens_per_s": tps, "release_late_s": win["late_s"],
               "failed": win["failed"]}
        print(json.dumps(row), flush=True)
        if share >= 0.9 and not grows and not win["failed"]:
            knee = rate
    print(json.dumps({"knee_rate_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
