"""Composable model definition: dense / GQA / MLA / MoE / Mamba / hybrid /
encoder-decoder LMs from one config (pure JAX pytrees, functional apply).

Paths:
* ``forward``      — training forward (logits over the full sequence);
* ``prefill``      — fill caches, return last-position logits;
* ``decode_step``  — one token with caches (the serving inner loop);
* encoder-decoder (whisper): ``encode`` + decoder blocks with cross-attn.

Modality frontends (audio conv, vision patches) are stubs per the
assignment: callers pass precomputed embeddings via ``inputs_embeds``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from .attention import (
    attention_decode,
    attention_prefill,
    attention_train,
    init_attention,
    init_attn_cache,
)
from .layers import (
    dense,
    dense_init,
    embed,
    embedding_init,
    layernorm,
    layernorm_init,
    rmsnorm,
    rmsnorm_init,
    rope_freqs,
    swiglu,
    gelu,
)
from .mamba2 import (
    init_mamba,
    init_mamba_cache,
    mamba_decode,
    mamba_prefill,
    mamba_train,
)
from .moe import apply_moe, init_moe


@dataclass(frozen=True)
class MoECfg:
    n_routed: int
    n_shared: int
    top_k: int
    d_expert: int


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    ffn_gated: bool = True
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    attn_kind: str = "gqa"           # mha | gqa | mla | none
    qkv_bias: bool = False
    mla_kv_rank: int = 0
    mla_rope_dim: int = 64
    moe: MoECfg | None = None
    moe_every: int = 1
    mixer: str = "attn"              # attn | mamba | hybrid
    attn_every: int = 8
    d_inner: int = 0
    ssm_state: int = 0
    mamba_heads: int = 8
    cross_attention: bool = False    # decoder blocks get cross-attn (whisper)
    encoder_layers: int = 0          # >0: encoder-decoder
    encoder_len: int = 1500
    rope_theta: float = 10000.0
    max_seq: int = 8192
    tie_embeddings: bool = True
    scan_layers: bool = False    # scan-over-layers (stacked params layout)

    def mixer_kind(self, i: int) -> str:
        if self.mixer == "attn":
            return "attn"
        if self.mixer == "mamba":
            return "mamba"
        return "attn" if i % self.attn_every == self.attn_every // 2 else "mamba"

    def ffn_kind(self, i: int) -> str:
        if self.moe is not None and i % self.moe_every == self.moe_every - 1:
            return "moe"
        return "dense" if self.d_ff > 0 else "none"


def _norm_init(cfg, d=None):
    d = d or cfg.d_model
    return rmsnorm_init(d) if cfg.norm == "rmsnorm" else layernorm_init(d)


def _norm(cfg, p, x):
    return rmsnorm(p, x) if cfg.norm == "rmsnorm" else layernorm(p, x)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def _init_ffn(key, cfg, dtype):
    mult = 2 if cfg.ffn_gated else 1
    k1, k2 = jax.random.split(key)
    return {
        "wi": dense_init(k1, cfg.d_model, mult * cfg.d_ff, False, dtype),
        "wo": dense_init(k2, cfg.d_ff, cfg.d_model, False, dtype),
    }


def _init_block(key, cfg, i: int, dtype, cross: bool):
    ks = jax.random.split(key, 6)
    block: dict[str, Any] = {"norm1": _norm_init(cfg)}
    if cfg.mixer_kind(i) == "attn":
        block["attn"] = init_attention(ks[0], cfg, dtype)
    else:
        block["mamba"] = init_mamba(ks[0], cfg, dtype)
    if cross:
        block["norm_x"] = _norm_init(cfg)
        block["cross"] = init_attention(ks[1], cfg, dtype)
    if cfg.ffn_kind(i) == "moe":
        block["norm2"] = _norm_init(cfg)
        block["moe"] = init_moe(ks[2], cfg, dtype)
    elif cfg.ffn_kind(i) == "dense":
        block["norm2"] = _norm_init(cfg)
        block["ffn"] = _init_ffn(ks[2], cfg, dtype)
    return block


def init_model(key, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, cfg.n_layers + cfg.encoder_layers + 4)
    params: dict[str, Any] = {
        "embed": embedding_init(ks[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": _norm_init(cfg),
        "blocks": [
            _init_block(ks[2 + i], cfg, i, dtype, cfg.cross_attention)
            for i in range(cfg.n_layers)
        ],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.vocab, False, dtype)
    if cfg.encoder_layers > 0:
        params["enc_blocks"] = [
            _init_block(ks[2 + cfg.n_layers + i], cfg, i, dtype, cross=False)
            for i in range(cfg.encoder_layers)
        ]
        params["enc_norm"] = _norm_init(cfg)
    return params


def param_count(params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))


# --------------------------------------------------------------------------
# apply
# --------------------------------------------------------------------------


def _ffn_apply(p, cfg, x):
    if cfg.ffn_gated:
        h = dense(p["wi"], x)
        g, u = jnp.split(h, 2, axis=-1)
        return dense(p["wo"], swiglu(g, u))
    return dense(p["wo"], gelu(dense(p["wi"], x)))


def _block_train(p, cfg, i, x, positions, rope, causal, impl,
                 enc_out=None, enc_positions=None):
    h = _norm(cfg, p["norm1"], x)
    if cfg.mixer_kind(i) == "attn":
        h = attention_train(p["attn"], h, cfg, positions, rope,
                            causal=causal, impl=impl)
    else:
        h = mamba_train(p["mamba"], h, cfg, impl=impl)
    x = x + h
    if enc_out is not None and "cross" in p:
        h = _norm(cfg, p["norm_x"], x)
        h = _cross_attention(p["cross"], h, enc_out, cfg, positions,
                             enc_positions, rope, impl)
        x = x + h
    if cfg.ffn_kind(i) == "none":
        return x
    h = _norm(cfg, p["norm2"], x)
    if cfg.ffn_kind(i) == "moe":
        h = apply_moe(p["moe"], h, cfg)
    else:
        h = _ffn_apply(p["ffn"], cfg, h)
    return x + h


def _cross_attention(p, x, enc_out, cfg, positions, enc_positions, rope, impl):
    """Decoder->encoder attention (queries from x, KV from enc_out)."""
    from .attention import _sdpa, _rope_heads
    b, l, _ = x.shape
    le = enc_out.shape[1]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(p["wq"], x).reshape(b, l, hq, hd).transpose(0, 2, 1, 3)
    k = dense(p["wk"], enc_out).reshape(b, le, hkv, hd).transpose(0, 2, 1, 3)
    v = dense(p["wv"], enc_out).reshape(b, le, hkv, hd).transpose(0, 2, 1, 3)
    y = _sdpa(q, k, v, causal=False, offset=0, impl=impl)
    y = y.transpose(0, 2, 1, 3).reshape(b, l, -1)
    return dense(p["wo"], y)


def _logits(params, cfg, x):
    if cfg.tie_embeddings:
        return x @ params["embed"]["e"].T
    return dense(params["lm_head"], x)


def encode(params, cfg: ModelConfig, inputs_embeds, impl="xla"):
    """Encoder stack (bidirectional). inputs_embeds: [B, Le, d] — the
    modality frontend (audio conv / vision patches) is a stub upstream."""
    b, le, _ = inputs_embeds.shape
    rope = rope_freqs(cfg.head_dim, max(cfg.max_seq, le), cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(le), (b, le))
    x = inputs_embeds
    for i, blk in enumerate(params["enc_blocks"]):
        x = _block_train(blk, cfg, i, x, positions, rope, causal=False,
                         impl=impl)
    return _norm(cfg, params["enc_norm"], x)


def forward(params, cfg: ModelConfig, tokens=None, inputs_embeds=None,
            enc_out=None, impl="xla", remat: bool = False, mesh=None):
    """Training forward -> logits [B, L, vocab]. ``mesh`` enables MaxText-
    style activation sharding constraints (residual stream batch-sharded,
    logits vocab-sharded) so GSPMD never replicates the big tensors."""
    from ..dist.sharding import constrain
    x = embed(params["embed"], tokens) if inputs_embeds is None else inputs_embeds
    x = constrain(x, (("pod", "data"), None, None), mesh)
    b, l, _ = x.shape
    rope = rope_freqs(cfg.head_dim, max(cfg.max_seq, l), cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(l), (b, l))
    enc_positions = None
    if cfg.encoder_layers > 0 and enc_out is None:
        # encoder input stub: callers normally pass real frame embeddings
        enc_out = encode(params, cfg,
                         jnp.zeros((b, cfg.encoder_len, cfg.d_model), x.dtype),
                         impl=impl)

    def run_block(x, blk_i):
        blk, i = blk_i
        return _block_train(blk, cfg, i, x, positions, rope, causal=True,
                            impl=impl, enc_out=enc_out,
                            enc_positions=enc_positions)

    for i, blk in enumerate(params["blocks"]):
        if remat:
            x = jax.checkpoint(
                lambda x, blk=blk, i=i: _block_train(
                    blk, cfg, i, x, positions, rope, causal=True, impl=impl,
                    enc_out=enc_out, enc_positions=enc_positions))(x)
        else:
            x = run_block(x, (blk, i))
        x = constrain(x, (("pod", "data"), None, None), mesh)
    x = _norm(cfg, params["final_norm"], x)
    logits = _logits(params, cfg, x)
    return constrain(logits, (("pod", "data"), None, "model"), mesh)


# --------------------------------------------------------------------------
# serving paths
# --------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    caches = []
    for i in range(cfg.n_layers):
        if cfg.mixer_kind(i) == "attn":
            caches.append(init_attn_cache(cfg, batch, max_len, dtype))
        else:
            caches.append(init_mamba_cache(cfg, batch))
    return caches


def prefill(params, cfg: ModelConfig, tokens, cache, enc_out=None,
            inputs_embeds=None, impl="xla"):
    """Fill caches with the prompt; returns (last logits [B, vocab], cache)."""
    x = embed(params["embed"], tokens) if inputs_embeds is None else inputs_embeds
    b, l, _ = x.shape
    rope = rope_freqs(cfg.head_dim, max(cfg.max_seq, l), cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(l), (b, l))
    new_cache = []
    for i, blk in enumerate(params["blocks"]):
        h = _norm(cfg, blk["norm1"], x)
        if cfg.mixer_kind(i) == "attn":
            h, c = attention_prefill(blk["attn"], h, cfg, positions, rope,
                                     cache[i], impl=impl)
        else:
            h, c = mamba_prefill(blk["mamba"], h, cfg, cache[i], impl=impl)
        new_cache.append(c)
        x = x + h
        if enc_out is not None and "cross" in blk:
            h = _norm(cfg, blk["norm_x"], x)
            h = _cross_attention(blk["cross"], h, enc_out, cfg, positions,
                                 None, rope, impl)
            x = x + h
        if cfg.ffn_kind(i) != "none":
            h = _norm(cfg, blk["norm2"], x)
            h = (apply_moe(blk["moe"], h, cfg) if cfg.ffn_kind(i) == "moe"
                 else _ffn_apply(blk["ffn"], cfg, h))
            x = x + h
    x = _norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, x[:, -1]), new_cache


def extend(params, cfg: ModelConfig, tokens, cache, enc_out=None,
           impl="xla", length=None):
    """Chunked-prefill continuation: process a multi-token chunk against the
    existing caches. tokens: [B, L] -> (last logits [B, vocab], cache).

    ``length`` (traced [B] or scalar, optional) marks the true chunk length
    when ``tokens`` is right-padded to a bucket size: pad positions neither
    advance the caches (attention ``len`` / mamba state) nor pick the output
    logit, so the serving engine can jit one kernel per bucket instead of
    one per exact chunk length."""
    from .attention import attention_extend
    from .mamba2 import mamba_extend

    x = embed(params["embed"], tokens)
    b, l, _ = x.shape
    adv = None if length is None else \
        jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    rope = rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    new_cache = []
    for i, blk in enumerate(params["blocks"]):
        h = _norm(cfg, blk["norm1"], x)
        if cfg.mixer_kind(i) == "attn":
            h, c = attention_extend(blk["attn"], h, cfg, rope, cache[i],
                                    impl=impl, length=adv)
        else:
            h, c = mamba_extend(blk["mamba"], h, cfg, cache[i], impl=impl,
                                length=adv)
        new_cache.append(c)
        x = x + h
        if enc_out is not None and "cross" in blk:
            start = c["len"] - (l if adv is None else adv)
            pos = start[:, None] + jnp.arange(l)[None, :]
            h = _norm(cfg, blk["norm_x"], x)
            h = _cross_attention(blk["cross"], h, enc_out, cfg, pos, None,
                                 rope, impl)
            x = x + h
        if cfg.ffn_kind(i) != "none":
            h = _norm(cfg, blk["norm2"], x)
            h = (apply_moe(blk["moe"], h, cfg) if cfg.ffn_kind(i) == "moe"
                 else _ffn_apply(blk["ffn"], cfg, h))
            x = x + h
    x = _norm(cfg, params["final_norm"], x)
    if adv is None:
        last = x[:, -1]
    else:
        idx = jnp.broadcast_to((adv - 1)[:, None, None], (b, 1, x.shape[-1]))
        last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
    return _logits(params, cfg, last), new_cache


def _mask_cache(old, new, active):
    """Freeze cache rows of inactive slots (requests still prefilling in
    other iterations must not be disturbed by the batched decode)."""
    if active is None:
        return new

    def blend(o, n):
        m = active.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o.astype(n.dtype))

    return jax.tree.map(blend, old, new)


def decode_step(params, cfg: ModelConfig, token, cache, enc_out=None,
                impl="xla", active=None, attend=None):
    """One decode step. token: [B] int32 -> (logits [B, vocab], cache).
    ``active``: optional [B] bool — inactive slots' caches are left
    untouched (continuous batching with partially-filled slots).
    ``attend``: optional ``(i, p, h, rope, cache_i) -> (h, cache_i)`` that
    stands in for ``attention_decode`` at attention layer ``i``."""
    x = embed(params["embed"], token)[:, None, :]
    b = x.shape[0]
    rope = rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    new_cache = []
    for i, blk in enumerate(params["blocks"]):
        h = _norm(cfg, blk["norm1"], x)
        if cfg.mixer_kind(i) != "attn":
            h, c = mamba_decode(blk["mamba"], h, cfg, cache[i], impl=impl)
        elif attend is not None:
            h, c = attend(i, blk["attn"], h, rope, cache[i])
        else:
            h, c = attention_decode(blk["attn"], h, cfg, rope, cache[i],
                                    impl=impl)
        new_cache.append(_mask_cache(cache[i], c, active))
        x = x + h
        if enc_out is not None and "cross" in blk:
            pos = c["len"] - 1
            h = _norm(cfg, blk["norm_x"], x)
            h = _cross_attention(blk["cross"], h, enc_out, cfg,
                                 pos[:, None], None, rope, impl)
            x = x + h
        if cfg.ffn_kind(i) != "none":
            h = _norm(cfg, blk["norm2"], x)
            h = (apply_moe(blk["moe"], h, cfg) if cfg.ffn_kind(i) == "moe"
                 else _ffn_apply(blk["ffn"], cfg, h))
            x = x + h
    x = _norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, x[:, 0]), new_cache


# --------------------------------------------------------------------------
# scan-over-layers paths (stacked params — see models/stacked.py)
# --------------------------------------------------------------------------


def _block_serve(blk, cfg, j, x, mode, cache_j, rope, positions, enc_out,
                 impl):
    """One block in serving mode: mode in {prefill, decode, extend}."""
    from .attention import attention_decode, attention_extend, attention_prefill
    from .mamba2 import mamba_decode, mamba_extend, mamba_prefill

    h = _norm(cfg, blk["norm1"], x)
    if cfg.mixer_kind(j) == "attn":
        if mode == "prefill":
            h, c = attention_prefill(blk["attn"], h, cfg, positions, rope,
                                     cache_j, impl=impl)
        elif mode == "decode":
            h, c = attention_decode(blk["attn"], h, cfg, rope, cache_j,
                                    impl=impl)
        else:
            h, c = attention_extend(blk["attn"], h, cfg, rope, cache_j,
                                    impl=impl)
    else:
        if mode == "prefill":
            h, c = mamba_prefill(blk["mamba"], h, cfg, cache_j, impl=impl)
        elif mode == "decode":
            h, c = mamba_decode(blk["mamba"], h, cfg, cache_j, impl=impl)
        else:
            h, c = mamba_extend(blk["mamba"], h, cfg, cache_j, impl=impl)
    x = x + h
    if enc_out is not None and "cross" in blk:
        l = x.shape[1]
        pos = c["len"][:, None] - l + jnp.arange(l)[None, :]
        h = _norm(cfg, blk["norm_x"], x)
        h = _cross_attention(blk["cross"], h, enc_out, cfg, pos, None, rope,
                             impl)
        x = x + h
    if cfg.ffn_kind(j) != "none":
        h = _norm(cfg, blk["norm2"], x)
        h = (apply_moe(blk["moe"], h, cfg) if cfg.ffn_kind(j) == "moe"
             else _ffn_apply(blk["ffn"], cfg, h))
        x = x + h
    return x, c


def forward_scanned(params, cfg: ModelConfig, tokens=None, inputs_embeds=None,
                    enc_out=None, impl="xla", remat: bool = True, mesh=None):
    """Training forward over stacked params (lax.scan over layer steps)."""
    from ..dist.sharding import constrain
    from .stacked import layer_period

    x = embed(params["embed"], tokens) if inputs_embeds is None else inputs_embeds
    x = constrain(x, (("pod", "data"), None, None), mesh)
    b, l, _ = x.shape
    rope = rope_freqs(cfg.head_dim, max(cfg.max_seq, l), cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(l), (b, l))
    if cfg.encoder_layers > 0 and enc_out is None:
        enc_out = encode_scanned(
            params, cfg, jnp.zeros((b, cfg.encoder_len, cfg.d_model), x.dtype),
            impl=impl)
    p = layer_period(cfg)

    def body(x, slots):
        for j in range(p):
            x = _block_train(slots[j], cfg, j, x, positions, rope,
                             causal=True, impl=impl, enc_out=enc_out)
        x = constrain(x, (("pod", "data"), None, None), mesh)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, tuple(params["blocks_stacked"]))
    x = _norm(cfg, params["final_norm"], x)
    logits = _logits(params, cfg, x)
    return constrain(logits, (("pod", "data"), None, "model"), mesh)


def encode_scanned(params, cfg: ModelConfig, inputs_embeds, impl="xla",
                   mesh=None):
    from ..dist.sharding import constrain

    b, le, _ = inputs_embeds.shape
    rope = rope_freqs(cfg.head_dim, max(cfg.max_seq, le), cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(le), (b, le))
    x = inputs_embeds

    def body(x, slots):
        x = _block_train(slots[0], cfg, 0, x, positions, rope, causal=False,
                         impl=impl)
        return constrain(x, (("pod", "data"), None, None), mesh), None

    x, _ = jax.lax.scan(body, x, tuple(params["enc_stacked"]))
    return _norm(cfg, params["enc_norm"], x)


def _serve_scanned(params, cfg, x, cache_slots, mode, rope, positions,
                   enc_out, impl, mesh):
    from ..dist.sharding import constrain
    from .stacked import layer_period

    p = layer_period(cfg)

    def body(x, inp):
        slots, caches = inp
        new_c = []
        for j in range(p):
            x, c = _block_serve(slots[j], cfg, j, x, mode, caches[j], rope,
                                positions, enc_out, impl)
            new_c.append(c)
        x = constrain(x, (("pod", "data"), None, None), mesh)
        return x, tuple(new_c)

    x, new_cache = jax.lax.scan(
        body, x, (tuple(params["blocks_stacked"]), tuple(cache_slots)))
    return x, list(new_cache)


def prefill_scanned(params, cfg: ModelConfig, tokens, cache_slots,
                    enc_out=None, impl="xla", mesh=None):
    from ..dist.sharding import constrain

    x = embed(params["embed"], tokens)
    x = constrain(x, (("pod", "data"), None, None), mesh)
    b, l, _ = x.shape
    rope = rope_freqs(cfg.head_dim, max(cfg.max_seq, l), cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(l), (b, l))
    x, new_cache = _serve_scanned(params, cfg, x, cache_slots, "prefill",
                                  rope, positions, enc_out, impl, mesh)
    x = _norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, x[:, -1]), new_cache


def decode_step_scanned(params, cfg: ModelConfig, token, cache_slots,
                        enc_out=None, impl="xla", mesh=None):
    from ..dist.sharding import constrain

    x = embed(params["embed"], token)[:, None, :]
    x = constrain(x, (("pod", "data"), None, None), mesh)
    rope = rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    x, new_cache = _serve_scanned(params, cfg, x, cache_slots, "decode",
                                  rope, None, enc_out, impl, mesh)
    x = _norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, x[:, 0]), new_cache
