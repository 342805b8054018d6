"""Work counts from shapes and the table of peaks."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402


def test_search_eval_work_by_hand():
    # B=2 batches, P=3 mappings, 2 x 5 ops, W=2, C=4 chiplets, D=2
    ops, nbytes = work.search_eval_work(2, 3, 2, 5, 2, 4)
    t = 10
    assert ops == 2 * 3 * t * (16 + 3 + 2)
    reads = 3 * t * 8 + 3 * t * 4 + 5 * 5 * 4 + 4 * 6 * 4 + 2 * t * 61
    assert nbytes == reads + 2 * 3 * 8


def test_least_time_is_the_slower_roof():
    pk = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time_s(1000, 10, pk) == 10.0
    assert work.least_time_s(100, 100, pk) == 10.0


def test_peaks_table():
    pk = work.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in pk["source"]
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_lm_flops():
    cfg = dict(d_model=8, n_heads=2, n_kv_heads=2, head_dim=4, d_ff=16,
               ffn_gated=True, qkv_bias=True, n_layers=3, vocab=10)
    per_layer = 8 * 24 + 24 + 8 * 8 + 3 * 8 * 16
    assert work.lm_layer_params(cfg) == per_layer
    mats = 3 * per_layer + 80
    assert work.lm_token_flops(cfg, 5) == 2 * mats + 4 * 3 * 8 * 5
    # a span of tokens is the sum of its tokens
    assert work.lm_span_flops(cfg, 4, 3) == sum(
        work.lm_token_flops(cfg, c) for c in (5, 6, 7))


def test_qwen_parameter_count():
    import json

    cfg = json.loads((ROOT / "bench/configs/qwen1.5-0.5b.json").read_text())
    m = cfg["model"]
    total = m["n_layers"] * (work.lm_layer_params(m) + 2 * m["d_model"]) \
        + m["d_model"] + m["vocab"] * m["d_model"]
    assert total == cfg["published"]["parameters"]
