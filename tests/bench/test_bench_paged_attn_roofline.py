"""``paged_attn_roofline``: the least work of the traced window's decode
steps (``work.paged_attention_work``) by hand, the reader on a synthetic
trace summary, nothing without a trace, and a traced CPU rehearsal of the
serving driver that reports a share in (0, 100]."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, serve, trace, work  # noqa: E402

import test_bench_serve_run as serve_run  # noqa: E402

NAME = "paged_attn_roofline"
MODEL = dict(n_layers=3, n_heads=4, n_kv_heads=2, head_dim=8)
KERNEL = "jit(decode_bs4)/jit(paged_decode_attention)/pallas_call"


def _read(rec):
    return harness.load_reader(NAME)(rec)


def test_least_work_by_hand():
    # two steps: 2 requests attending 10 positions, 3 attending 20
    ops, nbytes = work.paged_attention_work(MODEL, [(2, 10), (3, 20)], 4)
    assert ops == 4 * 3 * 4 * 8 * 30
    # per layer: K and V rows (2 heads x 8) of 30 positions, q and o
    # (4 heads x 8) of 5 requests, 4 bytes each
    assert nbytes == 3 * 4 * (2 * 16 * 30 + 2 * 32 * 5)


def _record(kernel_s=2e-3, other_s=5e-3):
    summary = trace.TraceSummary(window_s=1.0, busy_s=kernel_s + other_s,
                                 n_devices=1)
    summary.module_ops = {
        "jit_decode_bs4": {"custom-call.7": kernel_s, "fusion.3": other_s},
        "jit_prefill_bs1_c16": {"custom-call.7": 9.0}}
    names = {"jit_decode_bs4": {"custom-call.7": KERNEL,
                                "fusion.3": "jit(decode_bs4)/add"}}
    steps = [(0.1, 0.2, 4, 0.0, 400), (0.2, 0.3, 3, 0.0, 300)]
    return {"trace": summary, "op_names": names, "traced_decodes": steps,
            "model": dict(MODEL, n_layers=24, n_heads=16, n_kv_heads=16,
                          head_dim=64),
            "kv_itemsize": 4,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_reader_on_a_synthetic_summary():
    rec = _record()
    ops, nbytes = work.paged_attention_work(rec["model"], [(4, 400),
                                                           (3, 300)], 4)
    want = 100.0 * max(ops / 197e12, nbytes / 819e9) / 2e-3
    # only the kernel's scope in the decode entries counts: not the other
    # operations of the step, nor a prefill program's op of the same name
    assert _read(rec) == pytest.approx(want, rel=1e-12)
    assert 0 < _read(rec) <= 100


def test_nothing_to_read_gives_nothing():
    rec = _record()
    assert _read(dict(rec, trace=None)) is None
    assert _read(dict(rec, op_names={})) is None
    assert _read(dict(rec, traced_decodes=[])) is None
    # a model whose decode entries run no such kernel
    names = {"jit_decode_bs4": {"custom-call.7": "jit(decode_bs4)/add",
                                "fusion.3": "jit(decode_bs4)/add"}}
    assert _read(dict(rec, op_names=names)) is None
    for key in ("trace", "op_names", "traced_decodes"):
        assert _read({k: v for k, v in rec.items() if k != key}) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(harness, "OUT_DIR", tmp_path_factory.mktemp("bench_out"))
        mp.setattr(work, "peaks", lambda kind: {
            "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
        mp.setattr(trace, "DEVICE_PLANE", "^/host:CPU$")
        mp.setattr(trace, "DEVICE_LINES", ("tf_XLA",))
        cell = serve_run.tiny_cell()
        cell.traffic.update(warmup_s=0.0)   # decode steps while traced
        out = serve.run(serve_run.ctx(cell, True, seconds=3.0))
    finally:
        mp.undo()
    return cell, out


def test_traced_rehearsal_reports_a_share(traced):
    cell, out = traced
    assert NAME in {m["name"] for m in cell.per_layer}
    rec = dict(out.record, trace=out.trace)
    value = _read(rec)
    assert value is not None and 0 < value <= 100
    # every decode entry the run warmed, read from its HLO
    assert set(rec["op_names"]) == {
        f"jit_decode_bs{b}"
        for b in serve.buckets(cell.config["service"]["max_batch"])}
    assert all(d[4] >= d[2] for d in rec["traced_decodes"])
