"""CPU rehearsal of the serving cell at a tiny size: the open-loop window,
result line and reference comparison end to end, skipping only the look
for a chip; planted faults in the served tokens turn ``correct`` false."""
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, serve, traffic, trace, work  # noqa: E402

CELL = "serve.qwen1.5-0.5b.sharegpt-backlog"


@pytest.fixture()
def cpu_bench(monkeypatch):
    monkeypatch.setattr(work, "peaks", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(trace, "DEVICE_PLANE", "^/host:CPU$")
    monkeypatch.setattr(trace, "DEVICE_LINES", ("tf_XLA",))


def tiny_cell():
    cell = harness.resolve_cell(harness.load_benchmark(), CELL)
    cell.config["model"].update(vocab=512, d_model=64, n_layers=2,
                                n_heads=4, n_kv_heads=4, head_dim=16,
                                d_ff=128, max_seq=256)
    cell.config["service"].update(max_batch=4, max_len=96)
    cell.traffic.update(max_len=96, max_prompt=40, chunk=16, warmup_s=0.5,
                        n_requests=16, trace_seconds=1.0, check_requests=3)
    return cell


def ctx(cell, traced=False, seconds=2.0):
    return harness.RunContext(cell, 2 ** 40 + 3, seconds, traced,
                              time.perf_counter())


@pytest.mark.parametrize("traced", [False, True])
def test_serve_window_end_to_end(cpu_bench, traced):
    import jax

    cell = tiny_cell()
    # as deep a backlog as the cell's: 16 tiny requests can all finish
    # within the warm-up on a fast CPU, leaving the window empty
    cell.traffic.update(n_requests=64)
    line = harness.run_cell(ctx(cell, traced), jax.devices())
    json.dumps(line)
    keys = {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert set(line) == keys | ({"breakdown"} if traced else set())
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    m = line["metrics"]
    if traced:
        assert set(m) == {x["name"] for x in cell.per_layer}
        assert 0 < m["serve_mfu_pct"]["value"] < 100
    else:
        assert set(m) == {"output_tokens_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in m.values())


def _second_token(req, tok):
    # called after the decode step appended the request's second token
    return (tok + 1) % 512 if len(req.generated) == 2 else tok


def _half_batch(state={}):
    def alter(req, tok):
        # the second half of each decode batch gets the first half's token
        if req.rid % 2:
            return state.get("last", tok)
        state["last"] = tok
        return tok
    return alter


@pytest.mark.parametrize("fault", ["token_altered", "half_batch_left_out"])
def test_planted_fault_is_not_correct(cpu_bench, fault):
    alter = _second_token if fault == "token_altered" else _half_batch()
    out = serve.run(ctx(tiny_cell()), alter=alter)
    (name, value, limit), = out.checks
    assert value > limit, (fault, value, limit)


def test_same_seed_same_mix_other_seed_same_work():
    cell = tiny_cell()
    for rate in (None, 3.0):
        a = traffic.serve_requests(cell.traffic, 5, 3.0, 512, rate)
        b = traffic.serve_requests(cell.traffic, 5, 3.0, 512, rate)
        c = traffic.serve_requests(cell.traffic, 6, 3.0, 512, rate)
        assert a == b and a != c
        assert sorted((len(p), n) for _, p, n in a) == \
            sorted((len(p), n) for _, p, n in c)
        assert all(len(p) + n <= 96 for _, p, n in a)
        due = [d for d, _, _ in a]
        assert due == sorted(due)
        if rate is None:
            assert len(a) == 16 and max(due) == 0.0
        else:
            assert len(a) == round(3.0 * 3.5) and max(due) < 3.5


def test_control_reads_above_the_program(cpu_bench):
    out = serve.run(ctx(tiny_cell()), control=True)
    (name, value, limit), = out.checks
    assert value <= limit
    assert out.record["control"] > value
