"""The per-layer metrics that read the program's own spans
(``bench/program.py``): a traced CPU rehearsal of each driver reports
them, the inside and outside timings agree, and against a program without
``repro.telemetry`` each reads nothing; the program's spans leave the
harness's trace reduction as it was."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, program, search, serve, trace, work  # noqa: E402

import test_bench_search_run as search_run  # noqa: E402
import test_bench_serve_run as serve_run  # noqa: E402

SEARCH_METRICS = ["eval_host_ms_per_gen", "eval_fetch_ms_per_gen",
                  "ga_ops_ms_per_gen", "search_setup_pct",
                  "search_oracle_pct", "order_cache_hit_pct"]
SERVE_METRICS = ["decode_host_ms", "decode_fetch_ms",
                 "engine_host_ms_per_iter"]


def _traced_record(driver, cell, ctx, monkeypatch):
    monkeypatch.setattr(work, "peaks", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(trace, "DEVICE_PLANE", "^/host:CPU$")
    monkeypatch.setattr(trace, "DEVICE_LINES", ("tf_XLA",))
    out = driver.run(ctx)
    rec = dict(out.record, trace=out.trace)
    program.spans(rec)       # the run's spans, read before the next run's
    return rec


@pytest.fixture(scope="module")
def records(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    try:
        # a trace directory of its own: the drivers' rehearsals in other
        # test files trace the same cells, maybe at the same time
        mp.setattr(harness, "OUT_DIR", tmp_path_factory.mktemp("bench_out"))
        cells = {"search": search_run.tiny_cell(),
                 "serve": serve_run.tiny_cell()}
        recs = {"search": _traced_record(
                    search, cells["search"],
                    search_run.ctx(cells["search"], True), mp),
                "serve": _traced_record(
                    serve, cells["serve"],
                    serve_run.ctx(cells["serve"], True), mp)}
    finally:
        mp.undo()
    return cells, recs


def _read(name, rec):
    return harness.load_reader(name)(rec)


@pytest.mark.parametrize("kind,names", [("search", SEARCH_METRICS),
                                        ("serve", SERVE_METRICS)])
def test_traced_rehearsal_reports_every_program_metric(records, kind, names):
    cells, recs = records
    listed = {m["name"] for m in cells[kind].per_layer}
    assert set(names) <= listed
    for name in names:
        value = _read(name, recs[kind])
        assert value is not None and np.isfinite(value), name
        assert value >= 0, name
    if kind == "search":
        for name in ("search_setup_pct", "search_oracle_pct",
                     "order_cache_hit_pct"):
            assert _read(name, recs[kind]) <= 100


def test_inside_and_outside_timings_agree(records):
    _, recs = records
    s = recs["search"]
    inside = _read("eval_host_ms_per_gen", s) + \
        _read("eval_fetch_ms_per_gen", s)
    assert abs(inside - _read("eval_ms_per_gen", s)) < 0.5
    v = recs["serve"]
    inside = _read("decode_host_ms", v) + _read("decode_fetch_ms", v)
    assert abs(inside - _read("decode_iter_ms", v)) < 2.0


@pytest.mark.parametrize("name", SEARCH_METRICS + SERVE_METRICS)
def test_a_program_without_telemetry_reads_nothing(records, monkeypatch,
                                                   name):
    _, recs = records
    kind = "search" if name in SEARCH_METRICS else "serve"
    rec = {k: v for k, v in recs[kind].items() if k != program.KEY}
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert _read(name, rec) is None
    assert _read(name, {}) is None


def test_readers_on_planted_spans():
    from repro.telemetry import SpanRecord as S

    ms = 1_000_000
    recs = [
        S("repro.eval.orders", 1, 1 * ms, 2 * ms, {"hits": 6, "misses": 2}, 2),
        S("repro.eval.fetch", 1, 3 * ms, 7 * ms, {}, 3),
        S("repro.eval", None, 1 * ms, 8 * ms, {}, 1),
        S("repro.ga.step", None, 8 * ms, 10 * ms, {}, 4),
        S("repro.search.oracle", None, 10 * ms, 12 * ms, {}, 5),
        S("repro.eval", None, 50 * ms, 60 * ms, {}, 6),   # after the close
    ]
    rec = {"open": 0.0, "close": 0.02, "window_s": 0.02, program.KEY: recs}
    assert _read("eval_host_ms_per_gen", rec) == pytest.approx(3.0)
    assert _read("eval_fetch_ms_per_gen", rec) == pytest.approx(4.0)
    assert _read("ga_ops_ms_per_gen", rec) == pytest.approx(2.0)
    assert _read("search_oracle_pct", rec) == pytest.approx(10.0)
    assert _read("search_setup_pct", rec) == 0.0
    assert _read("order_cache_hit_pct", rec) == pytest.approx(75.0)


def test_program_spans_leave_the_harness_reduction_as_it_was(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import telemetry

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert telemetry.follow_profiler()
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.eval"):
                    with telemetry.span("repro.eval"):
                        f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.host"):
                    with telemetry.span("repro.ga.step"):
                        np.linalg.svd(np.ones((150, 150)))
    finally:
        jax.profiler.stop_trace()
        telemetry.follow_profiler()
    assert {r.name for r in telemetry.drain()} >= {"repro.eval",
                                                   "repro.ga.step"}
    s = trace.reduce_trace(trace.find_xplane(str(tmp_path)),
                           device_plane="^/host:CPU$",
                           device_lines=("tf_XLA",))
    assert set(s.span_s) == {"bench.eval", "bench.host"}
    assert s.span_count == {"bench.eval": 3, "bench.host": 3}
    # idle gaps are named by the innermost bench.* or repro.* span
    names = {n for n, _ in s.idle_gaps}
    assert names <= {"bench.eval", "bench.host", "repro.eval",
                     "repro.ga.step", "host"}
    assert "repro.ga.step" in names
