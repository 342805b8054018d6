"""The benchmark's plain references agree with the program where the
program is right, and their lower-precision controls do not."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.reference import mapping as ref  # noqa: E402
from bench.search import bf16  # noqa: E402

MODEL = dict(d_model=4096, n_heads=32, n_kv_heads=32, head_dim=128,
             d_ff=16384, n_layers=32, ffn_gated=False)
PKG = ref.Package(16384, 32 * 2 ** 20, (4, 4), ("WS", "OS") * 8, 32, 16, 8)
BATCHES = [
    [("prefill", 78, 78)],
    [("prefill", 512, 1024), ("decode", 1, 300), ("decode", 1, 41)],
    [("decode", 1, 700), ("decode", 1, 12), ("decode", 1, 95),
     ("decode", 1, 3000), ("decode", 1, 5)],
]


def _program_price(batch, mb, n_blocks, enc):
    from repro.core.evaluator import evaluate
    from repro.core.hardware import HardwareConfig
    from repro.core.timing import get_graph_and_tables
    from repro.core.workload import LLMSpec, Request

    spec = LLMSpec(name="gpt3-7b", vocab=50257, attn_kind="gqa", **MODEL)
    hw = HardwareConfig("L", PKG.grid, PKG.layout, 32, 16, 2, 2, 8)
    reqs = [Request(k, q, kv) for k, q, kv in batch]
    g, t = get_graph_and_tables(spec, reqs, hw, mb, n_blocks)
    r = evaluate(g, enc, hw, t)
    return g, r.latency_s, r.energy_j


@pytest.mark.parametrize("n_blocks", [None, 2])
@pytest.mark.parametrize("bi", range(len(BATCHES)))
def test_mapping_reference_matches_the_program_oracle(bi, n_blocks):
    from repro.core.encoding import random_encoding

    batch = BATCHES[bi]
    mb = 2 if any(k == "decode" for k, _, _ in batch) else 2
    rng = np.random.default_rng(bi)
    cols, ops, _ = ref.build_graph(MODEL, batch, mb, 8, n_blocks)
    enc = random_encoding(rng, len(ops), len(cols), 16)
    g, lat, en = _program_price(batch, mb, n_blocks, enc)
    assert (g.rows, g.n_cols) == (len(ops), len(cols))
    r_lat, r_en = ref.evaluate(MODEL, batch, mb, PKG, n_blocks,
                               enc.segmentation, enc.layer_to_chip)
    assert abs(r_lat - lat) / lat < 1e-12
    assert abs(r_en - en) / en < 1e-12
    # the control: the same arithmetic one precision below float32
    c_lat, c_en = ref.evaluate(MODEL, batch, mb, PKG, n_blocks,
                               enc.segmentation, enc.layer_to_chip, rnd=bf16)
    assert max(abs(c_lat - lat) / lat, abs(c_en - en) / en) > 1e-4


def _tiny_qwen():
    return dict(vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
                head_dim=16, d_ff=96, ffn_gated=True, norm="rmsnorm",
                attn_kind="gqa", qkv_bias=True, rope_theta=1000000.0,
                max_seq=128, tie_embeddings=True)


def test_qwen_reference_matches_the_program_forward():
    import jax
    import jax.numpy as jnp

    from bench.reference import qwen
    from bench.weights import make_params
    from repro.models.transformer import ModelConfig, forward

    model = _tiny_qwen()
    params = make_params(model, 7)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 40),
                       jnp.int32)
    want = qwen.logits(params, toks, **qwen.kwargs(model, 1e-6))
    with jax.default_matmul_precision("highest"):
        got = forward(params, ModelConfig(name="t", **model), toks[None])
    got = got[0] if isinstance(got, tuple) else got
    got = np.asarray(got).reshape(want.shape)
    assert np.max(np.abs(got - np.asarray(want))) < 1e-4
    low = qwen.logits(params, toks, **qwen.kwargs(model, 1e-6),
                      dtype=jnp.bfloat16)
    assert np.max(np.abs(np.asarray(low) - np.asarray(want))) > 1e-3


def test_token_gaps():
    import jax.numpy as jnp

    from bench.reference import qwen

    logits = jnp.asarray([[0.0, 2.0, 1.0], [5.0, 4.0, 3.0]])
    gaps = qwen.token_gaps(logits, jnp.asarray([2, 0]))
    assert np.allclose(np.asarray(gaps), [1.0, 0.0])
