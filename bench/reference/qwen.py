"""Plain reference forward pass of a Qwen1.5-style decoder.

The published Qwen1.5 block, written out in ``jax.numpy`` with no cache, no
batching and no kernels: RMSNorm (eps from the configuration), Q/K/V
projections with bias, rotary embeddings in the half-split (``rotate_half``)
convention with the configuration's ``rope_theta``, causal softmax
attention, a SwiGLU feed-forward (gate and up halves of ``wi``), a final
RMSNorm and the output head tied to the embedding.

It reads the weights in the layout the benchmark generates them
(``bench/weights.py``) and imports nothing of the program under test.
Matrix products run at ``precision`` (``highest`` for the reference, so
float32 means float32 on a TPU too); ``dtype`` casts the weights and the
activations (``bfloat16`` is the control of the serving comparison).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """x: [S, H, D] rotated by position, half-split convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [S, D/2]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(x.dtype)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim",
                                   "theta", "eps", "dtype", "precision"))
def logits(params, tokens, *, n_heads, n_kv_heads, head_dim, theta, eps,
           dtype=jnp.float32, precision="highest"):
    """tokens [S] -> float32 logits [S, vocab] of the causal forward pass."""
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    mm = partial(jnp.matmul, precision=precision)
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = p["embed"]["e"][tokens]
    rep = n_heads // n_kv_heads
    causal = pos[None, :] <= pos[:, None]
    for blk in p["blocks"]:
        a = blk["attn"]
        h = _rms(x, blk["norm1"]["g"], eps)
        q = (mm(h, a["wq"]["w"]) + a["wq"]["b"]).reshape(s, n_heads, head_dim)
        k = (mm(h, a["wk"]["w"]) + a["wk"]["b"]).reshape(s, n_kv_heads,
                                                          head_dim)
        v = (mm(h, a["wv"]["w"]) + a["wv"]["b"]).reshape(s, n_kv_heads,
                                                          head_dim)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=precision)
        sc = sc.astype(jnp.float32) / jnp.sqrt(jnp.float32(head_dim))
        w = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", w.astype(dtype), v,
                       precision=precision).reshape(s, -1)
        x = x + mm(o, a["wo"]["w"])
        f = blk["ffn"]
        h = _rms(x, blk["norm2"]["g"], eps)
        g, u = jnp.split(mm(h, f["wi"]["w"]), 2, axis=-1)
        x = x + mm(jax.nn.silu(g) * u, f["wo"]["w"])
    x = _rms(x, p["final_norm"]["g"], eps)
    return mm(x, p["embed"]["e"].T).astype(jnp.float32)


def kwargs(model: dict, eps: float) -> dict:
    return dict(n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
                head_dim=model["head_dim"], theta=float(model["rope_theta"]),
                eps=float(eps))


@jax.jit
def token_gaps(ref_logits, chosen):
    """How far each chosen token's logit lies below the best: [S]."""
    picked = jnp.take_along_axis(ref_logits, chosen[:, None], axis=1)[:, 0]
    return jnp.max(ref_logits, axis=1) - picked
