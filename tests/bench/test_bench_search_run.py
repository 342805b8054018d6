"""CPU rehearsal of a search cell: the harness's window, result line and
comparison run end to end at a tiny size, skipping only the look for a
chip, and planted faults of the timed path turn ``correct`` false."""
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, search, trace, work  # noqa: E402

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture()
def cpu_bench(monkeypatch):
    monkeypatch.setattr(work, "peaks", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(trace, "DEVICE_PLANE", "^/host:CPU$")
    monkeypatch.setattr(trace, "DEVICE_LINES", ("tf_XLA",))


def tiny_cell(name="search.gpt3-7b.sharegpt-window1"):
    cell = harness.resolve_cell(harness.load_benchmark(), name)
    cell.config["ga"].update(population=8, generations=3)
    cell.traffic["stream"]["n_requests"] = 12
    cell.traffic["rollout_iters"] = 5
    cell.traffic["check_samples"] = 6
    return cell


def ctx(cell, traced=False, seconds=1.5):
    return harness.RunContext(cell, 2 ** 33 + 17, seconds, traced,
                              time.perf_counter())


@pytest.mark.parametrize("traced", [False, True])
def test_search_window_end_to_end(cpu_bench, traced):
    import jax

    cell = tiny_cell()
    line = harness.run_cell(ctx(cell, traced), jax.devices())
    json.dumps(line)
    assert set(line) == KEYS | ({"breakdown"} if traced else set())
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    m = line["metrics"]
    if traced:
        assert set(m) == {x["name"] for x in cell.per_layer}
        assert 0 < m["mapping_eval_roofline"]["value"] < 100
        assert 0 < m["search_mfu_pct"]["value"] < 100
        assert line["device"]["busy_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(m) == {"search_evals_per_s", "setup_s"}
        assert m["search_evals_per_s"]["value"] > 0


def _half_left_out(lat, en):
    half = lat.shape[1] // 2
    lat, en = lat.copy(), en.copy()
    lat[:, half:] = lat[:, :half].mean(axis=1, keepdims=True)
    en[:, half:] = en[:, :half].mean(axis=1, keepdims=True)
    return lat, en


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
def test_planted_fault_is_not_correct(cpu_bench, fault):
    alter = {"answer_altered": lambda lat, en: (lat * (1 + 1e-3), en),
             "half_left_out": _half_left_out}[fault]
    out = search.run(ctx(tiny_cell()), alter=alter)
    (name, value, limit), = out.checks
    assert value > limit, (fault, value, limit)


def test_control_reads_above_the_program(cpu_bench):
    out = search.run(ctx(tiny_cell()), control=True)
    (name, value, limit), = out.checks
    assert value <= limit
    assert out.record["control"] > 10 * max(value, 1e-9)


def test_work_is_counted_the_same_whatever_backend_runs(cpu_bench,
                                                        monkeypatch):
    cell = tiny_cell("search.gpt3-7b.sharegpt-full16")
    cell.traffic["stream"]["n_requests"] = 4
    cell.traffic["rollout_iters"] = 2
    cell.config["ga"].update(population=4, generations=1)
    counts = {}
    for be in ("dense", "fused"):
        monkeypatch.setenv("REPRO_TIMING_BACKEND", be)
        out = search.run(ctx(cell, seconds=0.5))
        calls = out.record["calls"]
        assert calls
        shapes = {c[2:] for c in calls}
        counts[be] = {s: work.search_eval_work(*s[:4], 8, 16)
                      for s in shapes}
    common = set(counts["dense"]) & set(counts["fused"])
    assert common
    for s in common:
        assert counts["dense"][s] == counts["fused"][s]
