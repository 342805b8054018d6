"""Seeded random weights of a dense decoder, made on the device in one
jitted call, in the parameter layout the program serves (per block:
``norm1``, ``attn`` with ``wq/wk/wv`` (+ bias) and ``wo``, ``norm2``,
``ffn`` with ``wi`` (gate and up halves) and ``wo``; a tied ``embed``).

Matrices are N(0, 1/fan_in), the embedding N(0, 0.02^2), biases
N(0, 0.1^2) and norm gains 1 + N(0, 0.1^2), so every term of the block
takes part in the result.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("vocab", "d", "layers", "h", "kvh", "hd",
                                   "d_ff", "bias", "dtype"))
def _init(key, *, vocab, d, layers, h, kvh, hd, d_ff, bias, dtype):
    keys = iter(jax.random.split(key, 2 + 12 * layers))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def dense(d_in, d_out, with_bias=False):
        p = {"w": normal((d_in, d_out), d_in ** -0.5)}
        if with_bias:
            p["b"] = normal((d_out,), 0.1)
        return p

    def gain():
        return {"g": 1.0 + normal((d,), 0.1)}

    blocks = []
    for _ in range(layers):
        blocks.append({
            "norm1": gain(),
            "attn": {"wq": dense(d, h * hd, bias), "wk": dense(d, kvh * hd, bias),
                     "wv": dense(d, kvh * hd, bias), "wo": dense(h * hd, d)},
            "norm2": gain(),
            "ffn": {"wi": dense(d, 2 * d_ff), "wo": dense(d_ff, d)},
        })
    return {"embed": {"e": normal((vocab, d), 0.02)}, "final_norm": gain(),
            "blocks": blocks}


def make_params(model: dict, seed: int, dtype=jnp.float32):
    if not (model["ffn_gated"] and model["tie_embeddings"]
            and model["norm"] == "rmsnorm" and model["attn_kind"] == "gqa"):
        raise ValueError("weights.make_params makes gated, tied, RMSNorm "
                         "attention decoders only")
    return _init(jax.random.key(seed), vocab=model["vocab"],
                 d=model["d_model"], layers=model["n_layers"],
                 h=model["n_heads"], kvh=model["n_kv_heads"],
                 hd=model["head_dim"], d_ff=model["d_ff"],
                 bias=bool(model["qkv_bias"]), dtype=dtype)
