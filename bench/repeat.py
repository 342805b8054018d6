#!/usr/bin/env python3
"""Run cells several times in a row, each run its own process, and keep
what each printed:

    python3 bench/repeat.py --out chiprun_out/full32 \\
        search.gpt3-7b.sharegpt-full16:11,12,13 serve.qwen1.5-0.5b.sharegpt-backlog:21:1

Each argument is ``workload:seed[,seed...][:trace]``. ``--seconds``
defaults to ``run_seconds`` of ``BENCHMARK.json``. Runs go one after the
other (one process holds the chip at a time). Each run's standard output
and error go to ``<out>/<workload>.<seed>.<trace>.{out,err}``; the result
line and the check lines of every run are printed, and a summary of the
metrics per cell at the end. This process never imports JAX.
"""
from __future__ import annotations

import argparse
import json
import signal
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def spread(values):
    """Interquartile range over the median (``statistics.quantiles``)."""
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("runs", nargs="+")
    ap.add_argument("--out", default="chiprun_out/repeat")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or bench["run_seconds"]
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)
    results: dict = {}
    for spec in args.runs:
        parts = spec.split(":")
        name, seeds = parts[0], [int(s) for s in parts[1].split(",")]
        trace = parts[2] if len(parts) > 2 else "0"
        for seed in seeds:
            cmd = [sys.executable, "-X", "faulthandler", "bench/run.py",
                   "--workload", name, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", trace]
            t0 = time.perf_counter()
            p = subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE, text=True)
            try:
                so, se = p.communicate(timeout=args.timeout)
                rc = p.returncode
            except subprocess.TimeoutExpired:
                # SIGABRT makes the faulthandler print every thread's stack
                p.send_signal(signal.SIGABRT)
                try:
                    so, se = p.communicate(timeout=20)
                except subprocess.TimeoutExpired:
                    p.kill()
                    so, se = p.communicate()
                rc = 124
            took = time.perf_counter() - t0
            stem = out / f"{name}.{seed}.{trace}"
            stem.with_suffix(stem.suffix + ".out").write_text(so)
            stem.with_suffix(stem.suffix + ".err").write_text(se)
            last = so.strip().splitlines()[-1] if so.strip() else ""
            print(f"== {name} seed {seed} trace {trace}: rc {rc} in "
                  f"{took:.1f}s", flush=True)
            for ln in se.splitlines():
                if ln.startswith(("check ", "bench: [", "bench: refused", "Traceback",
                                  "Fatal Python", "Thread ", "  File")) \
                        or "Error" in ln[:40]:
                    print("   " + ln[:600])
            print("   " + last[:1500], flush=True)
            try:
                line = json.loads(last)
            except ValueError:
                continue
            for k, v in line.get("metrics", {}).items():
                results.setdefault((name, trace, k), []).append(v["value"])
            results.setdefault((name, trace, "correct"), []).append(
                float(line.get("correct", False)))
    print("== summary (median, spread = IQR / median, n)")
    for (name, trace, k), vals in sorted(results.items()):
        print(f"   {name} trace {trace} {k}: median "
              f"{statistics.median(vals)!r} spread {spread(vals):.4f} "
              f"n {len(vals)} values {vals}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
