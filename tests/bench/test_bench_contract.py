"""The benchmark's own files: BENCHMARK.json resolves by name to its
configurations, traffic mixes and metric readers, a new cell and a new
metric are picked up as files alone, and the command refuses to run
without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench", "tests/bench"]


def test_names_units_and_entry_keys():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in BENCH["end_to_end"]
                + BENCH["per_layer"]}) == len(BENCH["end_to_end"]) + \
        len(BENCH["per_layer"])
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.resolve_cell(BENCH, cell)
    assert c.traffic["driver"] in ("search", "serve")
    assert (ROOT / "bench" / f"{c.traffic['driver']}.py").exists()
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in reported
        assert callable(harness.load_reader(m["name"]))
    for lim in c.traffic["limits"].values():
        assert isinstance(lim, float) and lim > 0


def test_every_config_is_used_and_its_file_is_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        assert (ROOT / c["file"]).exists()


def test_run_seconds_fit_the_check_with_24_cells():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_new_cell_and_metric_are_files_only(tmp_path):
    """A later PR adds a traffic mix, a cell and a per-layer metric by
    adding files and entries; the harness finds them by name."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = next(w for w in bench["workloads"] if w["config"] == "gpt3-7b.L16")
    src = tmp_path / "bench" / "traffic" / f"{base['traffic']}.json"
    mix = json.loads(src.read_text())
    mix["stream"]["rate"] = 2.0
    (tmp_path / "bench" / "traffic" / "sharegpt-half-rate.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "eval_calls.py").write_text(
        "def read(rec):\n    return float(len(rec.get('calls', [])))\n")
    bench["workloads"].append({"name": "search.new", "config": "gpt3-7b.L16",
                               "traffic": "sharegpt-half-rate", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "eval_calls", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "population evaluator",
                               "moves": "search_evals_per_s",
                               "workloads": ["search.new"]})
    cell = harness.resolve_cell(bench, "search.new", tmp_path / "bench")
    assert cell.traffic["stream"]["rate"] == 2.0
    assert [m["name"] for m in cell.per_layer] == ["eval_calls"]
    read = harness.load_reader("eval_calls", tmp_path / "bench")
    assert read({"calls": [1, 2, 3]}) == 3.0


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_command_refuses_with_the_benchmark_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
