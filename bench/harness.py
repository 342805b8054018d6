"""The benchmark harness: everything that is not one driver's own.

It finds a cell's configuration, traffic mix and per-layer metric readers
by name (``BENCHMARK.json`` -> ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py``), refuses
to run without the accelerator, hands the cell to the driver its traffic
names, and prints the result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

Every run prints each number that decides ``correct`` beside its limit, as
the last lines on standard error and under ``checks`` (the last key) in the
one JSON line that ends standard output.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
OUT_DIR = ROOT / "bench_out"
WINDOW_SPAN = "bench.window"


class Refused(RuntimeError):
    """The run cannot start: no accelerator, or a malformed cell."""


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"bench: [{time.perf_counter() - _T0:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# finding a cell by name
# --------------------------------------------------------------------------


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.exists():
        raise Refused(f"{path} not found")
    return json.loads(path.read_text())


def _read_json(path: Path) -> dict:
    if not path.exists():
        raise Refused(f"{path.relative_to(path.parents[2])} not found")
    return json.loads(path.read_text())


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def resolve_cell(bench: dict, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; have "
                      f"{sorted(cells)}")
    w = cells[name]
    config = _read_json(bench_dir / "configs" / f"{w['config']}.json")
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(name, w, config, traffic, e2e, layer)


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    """``read(record) -> float | None`` of one per-layer metric."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.exists():
        raise Refused(f"no reader bench/metrics/{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# device, spans, compiles
# --------------------------------------------------------------------------


def require_accelerator(chips: int):
    """The device list when JAX sees at least ``chips`` TPU chips; refuses
    otherwise (there is no CPU fallback)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def local_devices():
    """The devices a run drives."""
    import jax

    return jax.devices()


def device_kind() -> str:
    return local_devices()[0].device_kind


def memory_peak_bytes(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class Spans:
    """Host spans of the harness around its calls into each layer: host
    clock intervals kept in memory and, in a traced run, the same spans as
    ``TraceAnnotation``s in the profiler's trace."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.items: list = []          # (name, t0, t1)

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.items.append((name, t0, t1))


class CompileCounter:
    """Counts programs lowered by JAX (compiled or read from the cache), and
    the persistent cache's hits and misses."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.cache = {"hits": 0, "misses": 0}
        monitoring.register_event_duration_secs_listener(self._on)
        monitoring.register_event_listener(self._on_event)

    def _on(self, name, _dur, **_kw):
        if name == self.EVENT:
            self.count += 1

    def _on_event(self, name, **_kw):
        for k in self.cache:
            if name == f"/jax/compilation_cache/cache_{k}":
                self.cache[k] += 1


class Tracer:
    """The profiler trace of a window, written inside the checkout and
    removed once reduced."""

    def __init__(self, workload: str):
        self.dir = OUT_DIR / "trace" / workload
        self._ann = None

    def start(self):
        """Start the profiler; it takes a moment, so before the window."""
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))

    def open_window(self):
        import jax

        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()

    def stop(self):
        import jax

        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, **kw):
        from bench import trace

        path = trace.find_xplane(str(self.dir))
        log(f"trace {path} ({os.path.getsize(path)} bytes)")
        summary = trace.reduce_trace(path, **kw)
        for line in summary.layout:
            log(f"trace plane {line[:400]}")
        log(f"trace spans {summary.span_count}; busy inside them "
            f"{summary.span_busy_s}")
        shutil.rmtree(self.dir, ignore_errors=True)
        return summary


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


@dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    traced: bool
    t_start: float


@dataclass
class RunOutput:
    t_open: float                       # window open (perf_counter)
    end_to_end: dict                    # name -> value (driver's metrics)
    record: dict                        # what the per-layer readers read
    checks: list                        # [(name, value, limit)]
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: object = None                # trace.TraceSummary
    notes: list = field(default_factory=list)


def seed_child(seed: int, *path: int) -> int:
    """A 32-bit seed derived from the run's seed (any whole number)."""
    import numpy as np

    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), *path])
    return int(ss.generate_state(1)[0])


def drop_program_knobs() -> list:
    """The benchmark measures the program as users run it: no ``REPRO_*``
    environment knob reaches it."""
    gone = sorted(k for k in os.environ if k.startswith("REPRO_"))
    for k in gone:
        del os.environ[k]
    return gone


CACHE_DIR = ROOT / ".jax_cache"


def setup_process(cell: Cell):
    """Everything a process does before its first run of a cell: no
    ``REPRO_*`` knob, the program importable, a TPU with the cell's chips,
    and JAX's persistent compilation cache in the checkout's fixed
    ``.jax_cache`` (given to the program through the variable it reads),
    where every program is kept, however fast it compiled."""
    dropped = drop_program_knobs()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.compile_cache import enable_compile_cache

    devs = require_accelerator(int(cell.workload["chips"]))
    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    log(f"compile cache {CACHE_DIR}; dropped knobs {dropped}")
    return devs


def run_cell(ctx: RunContext, devs) -> dict:
    """Drive one run and assemble its result line (a dict)."""
    driver = importlib.import_module(f"bench.{ctx.cell.traffic['driver']}")
    out: RunOutput = driver.run(ctx)
    correct = bool(out.checks) and out.failed == 0 \
        and all(v <= lim for _, v, lim in out.checks)
    if ctx.traced:
        rec = dict(out.record, trace=out.trace)
        metrics = {}
        for m in ctx.cell.per_layer:
            value = load_reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(out.end_to_end, setup_s=out.t_open - ctx.t_start)
        metrics = {}
        for m in ctx.cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(f"driver gave no {m['name']!r}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if ctx.traced and out.trace is not None:
        device["busy_s"] = out.trace.busy_s
        device["window_s"] = out.trace.window_s
        line["breakdown"] = {"device_ops": out.trace.device_ops,
                             "idle_gaps": out.trace.idle_gaps}
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, v, lim in out.checks}
    for note in out.notes:
        log(note)
    for n, v, lim in out.checks:
        print(f"check {n} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    return line


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = resolve_cell(load_benchmark(), args.workload)
        devs = setup_process(cell)
    except (Refused, ImportError) as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    log(f"{cell.name} seed {args.seed} on {devs[0].device_kind} "
        f"x{len(devs)}")
    line = run_cell(RunContext(cell, args.seed, args.seconds,
                               bool(args.trace), t_start), devs)
    print(json.dumps(line), flush=True)
    return 0
