"""``decode_kv_live_pct``: the share of the dense KV view that the window's
decode steps attend over, read from the ``kv_blocks_live`` /
``kv_blocks_dense`` attributes of the service's decode staging spans. A
traced CPU rehearsal of the serving driver reports it as a share, planted
spans read as their sums, and a program without them gives nothing."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, program, serve, trace, work  # noqa: E402

import test_bench_serve_run as serve_run  # noqa: E402

NAME = "decode_kv_live_pct"


def _read(rec):
    return harness.load_reader(NAME)(rec)


@pytest.fixture(scope="module")
def record(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(harness, "OUT_DIR", tmp_path_factory.mktemp("bench_out"))
        mp.setattr(work, "peaks", lambda kind: {
            "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
        mp.setattr(trace, "DEVICE_PLANE", "^/host:CPU$")
        mp.setattr(trace, "DEVICE_LINES", ("tf_XLA",))
        cell = serve_run.tiny_cell()
        out = serve.run(serve_run.ctx(cell, True))
        rec = dict(out.record, trace=out.trace)
        program.spans(rec)
    finally:
        mp.undo()
    return cell, rec


def test_traced_rehearsal_reports_a_share(record):
    cell, rec = record
    assert NAME in {m["name"] for m in cell.per_layer}
    value = _read(rec)
    assert value is not None and np.isfinite(value)
    # every real lane attends at least one block and at most its table
    assert 0 < value <= 100


def test_a_program_without_the_spans_reads_nothing(record, monkeypatch):
    _, rec = record
    bare = {k: v for k, v in rec.items() if k != program.KEY}
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert _read(bare) is None
    assert _read({}) is None


def test_reads_the_window_steps():
    """Live over dense KV blocks of the decode steps inside the window's
    decode calls; a step outside them, and stage spans without the
    counters (a program from before them), count for nothing."""
    from repro.telemetry import SpanRecord as S

    ms = 1_000_000
    recs = [
        S("repro.serve.decode.stage", 1, 1 * ms, 2 * ms,
          {"kv_blocks_live": 10, "kv_blocks_dense": 64}, 2),
        S("repro.serve.decode", None, 1 * ms, 5 * ms, {}, 1),
        S("repro.serve.decode.stage", 3, 6 * ms, 7 * ms,
          {"kv_blocks_live": 22, "kv_blocks_dense": 64}, 4),
        S("repro.serve.decode", None, 6 * ms, 9 * ms, {}, 3),
        S("repro.serve.decode.stage", 5, 50 * ms, 51 * ms,
          {"kv_blocks_live": 64, "kv_blocks_dense": 64}, 6),
        S("repro.serve.decode", None, 50 * ms, 52 * ms, {}, 5),
    ]
    rec = {"decodes": [(0.0, 0.006, 4, 0.0), (0.006, 0.01, 4, 0.0)],
           program.KEY: recs}
    assert _read(rec) == pytest.approx(25.0)
    bare = [r._replace(attrs={}) for r in recs]
    assert _read(dict(rec, **{program.KEY: bare})) is None
