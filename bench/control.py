#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's number and the
control's on several seeds, in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

For each seed it runs the cell's window as a benchmark run does and prints
the compared number of the program beside the control's: the same
reference carried out one precision below the configuration's (bfloat16
for the float32 the device evaluator and the served model state). A limit
lies above the program's readings and below the control's.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def readings(cell, seeds, seconds: float) -> list:
    driver = importlib.import_module(f"bench.{cell.traffic['driver']}")
    rows = []
    for seed in seeds:
        ctx = harness.RunContext(cell, seed, seconds, False,
                                 time.perf_counter())
        out = driver.run(ctx, control=True)
        name, value, limit = out.checks[0]
        rows.append({"seed": seed, "check": name, "program": value,
                     "control": out.record["control"], "limit": limit})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    harness.setup_process(cell)

    rows = readings(cell, [int(s) for s in args.seeds.split(",")],
                    args.seconds)
    prog = [r["program"] for r in rows]
    ctrl = [r["control"] for r in rows]
    print(f"program: max {max(prog)!r} over {len(prog)} seeds; control: "
          f"min {min(ctrl)!r}; ratio {min(ctrl) / max(max(prog), 1e-300)!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
