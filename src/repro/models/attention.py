"""Attention mixers: MHA / GQA / MLA, train + prefill + decode paths.

Two implementations per path:
* ``impl="xla"`` — pure jnp (differentiable; chunked online-softmax scan for
  long sequences so the score matrix never materialises);
* ``impl="pallas"`` — the Pallas kernels (serving path; interpret mode on CPU).

MLA (DeepSeek-V2) caches the shared compressed latent (kv_rank + rope_dim
per token) and uses the absorbed form at decode time: queries are projected
into the latent space, so decode attends over a single shared latent "KV
head" — the memory win that lets MLA serve 128-head attention at a fraction
of the GQA cache cost.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from .layers import apply_rope, dense, dense_init

# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_attention(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    if cfg.attn_kind == "mla":
        r, rd = cfg.mla_kv_rank, cfg.mla_rope_dim
        return {
            "wq": dense_init(ks[0], d, hq * (hd + rd), cfg.qkv_bias, dtype),
            "w_dkv": dense_init(ks[1], d, r + rd, cfg.qkv_bias, dtype),
            "w_uk": dense_init(ks[2], r, hq * hd, False, dtype),
            "w_uv": dense_init(ks[3], r, hq * hd, False, dtype),
            "wo": dense_init(ks[4], hq * hd, d, False, dtype),
        }
    return {
        "wq": dense_init(ks[0], d, hq * hd, cfg.qkv_bias, dtype),
        "wk": dense_init(ks[1], d, hkv * hd, cfg.qkv_bias, dtype),
        "wv": dense_init(ks[2], d, hkv * hd, cfg.qkv_bias, dtype),
        "wo": dense_init(ks[3], hq * hd, d, False, dtype),
    }


# --------------------------------------------------------------------------
# scaled-dot-product attention backends
# --------------------------------------------------------------------------


def _plain_attention(q, k, v, causal: bool, offset: int):
    """q: [B,H,Lq,D], k/v: [B,H,Lk,D] (heads already repeated)."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / np.sqrt(d)
    if causal:
        qi = jnp.arange(q.shape[2])[:, None] + offset
        ki = jnp.arange(k.shape[2])[None, :]
        s = jnp.where(ki <= qi, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _chunked_attention(q, k, v, causal: bool, offset: int, chunk: int = 512):
    """Online-softmax scan over kv chunks — flash semantics in pure XLA, so
    the [Lq, Lk] score matrix never materialises (needed for 32k+ prefill).
    Differentiable (lax.scan)."""
    b, h, lq, d = q.shape
    dv = v.shape[-1]          # MLA: value head dim differs from qk dim
    lk = k.shape[2]
    n = -(-lk // chunk)
    pad = n * chunk - lk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = kp.reshape(b, h, n, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = vp.reshape(b, h, n, chunk, dv).transpose(2, 0, 1, 3, 4)
    scale = 1.0 / np.sqrt(d)
    qi = jnp.arange(lq)[:, None] + offset

    def step(carry, inp):
        m, l, acc = carry
        ci, kb, vb = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb).astype(jnp.float32) * scale
        kpos = ci * chunk + jnp.arange(chunk)[None, :]
        mask = kpos < lk
        if causal:
            mask = mask & (kpos <= qi)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l * alpha + p.sum(-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, h, lq, 1), -1e30, jnp.float32),
            jnp.zeros((b, h, lq, 1), jnp.float32),
            jnp.zeros((b, h, lq, dv), jnp.float32))
    # checkpoint the chunk body: the [lq, chunk] score tile is recomputed in
    # the backward pass instead of being saved per scan step (flash-style)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), init,
                                  (jnp.arange(n), kc, vc))
    return (acc / jnp.where(l == 0, 1.0, l)).astype(q.dtype)


def _sdpa(q, k, v, causal, offset, impl, chunk_threshold: int = 2048):
    rep = q.shape[1] // k.shape[1]
    if impl == "pallas":
        return ops.flash_attention(q, k, v, causal=causal)
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if max(q.shape[2], k.shape[2]) > chunk_threshold:
        return _chunked_attention(q, k, v, causal, offset)
    return _plain_attention(q, k, v, causal, offset)


# --------------------------------------------------------------------------
# projections
# --------------------------------------------------------------------------


def _rope_heads(x, positions, cos, sin):
    """x: [B, L, H, D] -> rotated, same layout. positions: [B, L]."""
    xt = x.transpose(0, 2, 1, 3)                   # [B, H, L, D]
    xt = apply_rope(xt, positions[:, None, :], cos, sin)
    return xt.transpose(0, 2, 1, 3)


def _project_qkv(p, x, cfg, positions, rope):
    """Returns q/k/v as [B, H, L, D] plus the MLA latent (for caching)."""
    b, l, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cos, sin = rope
    if cfg.attn_kind == "mla":
        r, rd = cfg.mla_kv_rank, cfg.mla_rope_dim
        qf = dense(p["wq"], x).reshape(b, l, hq, hd + rd)
        q_nope, q_rope = qf[..., :hd], qf[..., hd:]
        q_rope = _rope_heads(q_rope, positions, cos, sin)
        ckv = dense(p["w_dkv"], x)                  # [B, L, r+rd]
        c, k_rope = ckv[..., :r], ckv[..., r:]
        k_rope = _rope_heads(k_rope[:, :, None, :], positions, cos, sin)
        k_nope = (c @ p["w_uk"]["w"]).reshape(b, l, hq, hd)
        v = (c @ p["w_uv"]["w"]).reshape(b, l, hq, hd)
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, l, hq, rd))], -1)
        latent = jnp.concatenate([c, k_rope[:, :, 0, :]], -1)  # [B, L, r+rd]
        return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), latent)
    q = dense(p["wq"], x).reshape(b, l, hq, hd)
    k = dense(p["wk"], x).reshape(b, l, hkv, hd)
    v = dense(p["wv"], x).reshape(b, l, hkv, hd)
    q = _rope_heads(q, positions, cos, sin)
    k = _rope_heads(k, positions, cos, sin)
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), None)


# --------------------------------------------------------------------------
# forward paths
# --------------------------------------------------------------------------


def attention_train(p, x, cfg, positions, rope, causal=True, impl="xla"):
    """Full-sequence attention (training / encoder). x: [B, L, d]."""
    b, l, _ = x.shape
    q, k, v, _ = _project_qkv(p, x, cfg, positions, rope)
    y = _sdpa(q, k, v, causal, offset=0, impl=impl)
    y = y.transpose(0, 2, 1, 3).reshape(b, l, -1)
    return dense(p["wo"], y)


def attention_prefill(p, x, cfg, positions, rope, cache, impl="xla"):
    """Prefill: full-sequence attention + fill the KV cache."""
    b, l, _ = x.shape
    q, k, v, latent = _project_qkv(p, x, cfg, positions, rope)
    y = _sdpa(q, k, v, causal=True, offset=0, impl=impl)
    y = y.transpose(0, 2, 1, 3).reshape(b, l, -1)
    ln = jnp.full((b,), l, jnp.int32)
    if cfg.attn_kind == "mla":
        lat4 = latent[:, :, None, :]
        if cache["kv"].dtype == jnp.int8:
            qv, sc = _quantize_kv(lat4)
            kv = jax.lax.dynamic_update_slice(cache["kv"], qv, (0, 0, 0, 0))
            kvs = jax.lax.dynamic_update_slice(cache["kv_scale"], sc,
                                               (0, 0, 0))
            cache = {"kv": kv, "kv_scale": kvs, "len": ln}
        else:
            kv = jax.lax.dynamic_update_slice(
                cache["kv"], lat4.astype(cache["kv"].dtype), (0, 0, 0, 0))
            cache = {"kv": kv, "len": ln}
    else:
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        if cache["k"].dtype == jnp.int8:
            qk, sk = _quantize_kv(kt)
            qv, sv = _quantize_kv(vt)
            cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], qk,
                                                  (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], qv,
                                                  (0, 0, 0, 0)),
                "k_scale": jax.lax.dynamic_update_slice(cache["k_scale"], sk,
                                                        (0, 0, 0)),
                "v_scale": jax.lax.dynamic_update_slice(cache["v_scale"], sv,
                                                        (0, 0, 0)),
                "len": ln,
            }
        else:
            cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache["k"], kt.astype(cache["k"].dtype), (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], vt.astype(cache["v"].dtype), (0, 0, 0, 0)),
                "len": ln,
            }
    return dense(p["wo"], y), cache


def _scatter_scale(cache, new, pos):
    """cache: [B, S, H]; new: [B, H]; pos: [B] — blend, like _scatter_cache."""
    from ..tuning import cache_update_mode
    if cache_update_mode() == "scatter":
        b = cache.shape[0]
        return cache.at[jnp.arange(b), pos].set(new)
    s = cache.shape[1]
    oh = (jnp.arange(s)[None, :] == pos[:, None]).astype(cache.dtype)
    return cache * (1 - oh)[:, :, None] + oh[:, :, None] * new[:, None, :]


def _scatter_cache(cache, new, pos):
    """cache: [B, S, H, D]; new: [B, H, D]; pos: [B].

    Two implementations (repro.tuning REPRO_CACHE_UPDATE):
    * "blend" — one-hot blend: purely elementwise, stays sharded even when
      the sequence dim is model-sharded, but reads+writes the whole cache;
    * "scatter" — positional scatter: one write, requires the sequence dim
      to be shard-local (pair with REPRO_CACHE_SHARD=feature)."""
    from ..tuning import cache_update_mode
    if cache_update_mode() == "scatter":
        b = cache.shape[0]
        return cache.at[jnp.arange(b), pos].set(new.astype(cache.dtype))
    s = cache.shape[1]
    oh = (jnp.arange(s)[None, :] == pos[:, None]).astype(cache.dtype)
    return (cache * (1 - oh)[:, :, None, None]
            + oh[:, :, None, None] * new[:, None, :, :].astype(cache.dtype))


def attention_decode(p, x, cfg, rope, cache, impl="xla"):
    """One-token decode with KV cache. x: [B, 1, d] -> [B, 1, d]."""
    b = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cos, sin = rope
    pos = cache["len"]                              # [B]
    x1 = x[:, 0, :]

    if cfg.attn_kind == "mla":
        r, rd = cfg.mla_kv_rank, cfg.mla_rope_dim
        qf = dense(p["wq"], x1).reshape(b, hq, hd + rd)
        q_nope, q_rope = qf[..., :hd], qf[..., hd:]
        q_rope = apply_rope(q_rope, pos[:, None], cos, sin)
        # absorbed form: project q_nope into the latent space
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope,
                           p["w_uk"]["w"].reshape(r, hq, hd))
        q_eff = jnp.concatenate([q_lat, q_rope], -1)   # [B, Hq, r+rd]
        ckv = dense(p["w_dkv"], x1)
        c_new, kr_new = ckv[..., :r], ckv[..., r:]
        kr_new = apply_rope(kr_new[:, None, :], pos[:, None], cos, sin)[:, 0]
        lat_new = jnp.concatenate([c_new, kr_new], -1)[:, None, :]  # [B,1,r+rd]
        if cache["kv"].dtype == jnp.int8:
            qv, sc = _quantize_kv(lat_new[:, None, :, :].reshape(b, 1, 1, -1))
            kv = _scatter_cache(cache["kv"], qv[:, 0], pos)
            kv_scale = _scatter_scale(cache["kv_scale"], sc[:, 0], pos)
            cache = {"kv": kv, "kv_scale": kv_scale, "len": pos + 1}
            kv_f = _dequantize_kv(cache, "kv")
        else:
            kv = _scatter_cache(cache["kv"], lat_new, pos)
            cache = {"kv": kv, "len": pos + 1}
            kv_f = kv
        lengths = pos + 1
        if impl == "pallas" and cache["kv"].dtype != jnp.int8:
            o = ops.decode_attention(q_eff, kv_f, kv_f, lengths)
        else:
            o = _xla_decode(q_eff, kv_f, kv_f, lengths)
        o = o.astype(x.dtype)
        y = jnp.einsum("bhr,rhd->bhd", o[..., :r],
                       p["w_uv"]["w"].reshape(r, hq, hd))
        return dense(p["wo"], y.reshape(b, -1))[:, None, :], cache

    q, k, v = decode_qkv(p, x1, cfg, rope, pos)
    if cache["k"].dtype == jnp.int8:
        qk, sk = _quantize_kv(k[:, None])
        qv2, sv = _quantize_kv(v[:, None])
        cache = {"k": _scatter_cache(cache["k"], qk[:, 0], pos),
                 "v": _scatter_cache(cache["v"], qv2[:, 0], pos),
                 "k_scale": _scatter_scale(cache["k_scale"], sk[:, 0], pos),
                 "v_scale": _scatter_scale(cache["v_scale"], sv[:, 0], pos),
                 "len": pos + 1}
        kc = _dequantize_kv(cache, "k")
        vc = _dequantize_kv(cache, "v")
    else:
        kc = _scatter_cache(cache["k"], k, pos)
        vc = _scatter_cache(cache["v"], v, pos)
        cache = {"k": kc, "v": vc, "len": pos + 1}
    lengths = pos + 1
    if impl == "pallas" and cache["k"].dtype != jnp.int8:
        o = ops.decode_attention(q, kc, vc, lengths)
    else:
        o = _xla_decode(q, kc, vc, lengths)
    o = o.astype(x.dtype)
    return dense(p["wo"], o.reshape(b, -1))[:, None, :], cache


def decode_qkv(p, x1, cfg, rope, pos):
    """MHA/GQA projections of one new token per sequence, rotated at
    ``pos`` [B]. x1: [B, d] -> q [B, Hq, D], k and v [B, Hkv, D]."""
    b = x1.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cos, sin = rope
    q = dense(p["wq"], x1).reshape(b, hq, hd)
    k = dense(p["wk"], x1).reshape(b, hkv, hd)
    v = dense(p["wv"], x1).reshape(b, hkv, hd)
    q = apply_rope(q, pos[:, None], cos, sin)
    k = apply_rope(k, pos[:, None], cos, sin)
    return q, k, v


def _xla_decode(q, k_cache, v_cache, lengths):
    """q: [B, Hq, D]; caches: [B, S, Hkv, D]. Grouped-head einsums — the KV
    cache is never materialised per query head (with MLA's single latent
    head and 128 query heads a repeat would be a 128x blow-up)."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, d)
    logits = jnp.einsum("bgrd,bsgd->bgrs", qg,
                        k_cache).astype(jnp.float32) / np.sqrt(d)
    mask = jnp.arange(s)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, hq, d)


def attention_extend(p, x, cfg, rope, cache, impl="xla", length=None):
    """Multi-token cache extension (chunked prefill): the chunk's queries
    attend over the existing cache plus themselves. x: [B, L, d].

    ``length`` ([B], optional): true chunk length when x is right-padded —
    only the cache ``len`` advance uses it (pad KV entries land beyond the
    advanced length, are never read by the causal mask, and are overwritten
    by the next chunk)."""
    b, l, _ = x.shape
    adv = l if length is None else length
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cos, sin = rope
    off = cache["len"]                                   # [B]
    positions = off[:, None] + jnp.arange(l)[None, :]

    if cfg.attn_kind == "mla":
        r, rd = cfg.mla_kv_rank, cfg.mla_rope_dim
        qf = dense(p["wq"], x).reshape(b, l, hq, hd + rd)
        q_nope, q_rope = qf[..., :hd], qf[..., hd:]
        q_rope = _rope_heads(q_rope, positions, cos, sin)
        q_lat = jnp.einsum("blhd,rhd->blhr", q_nope,
                           p["w_uk"]["w"].reshape(r, hq, hd))
        q_eff = jnp.concatenate([q_lat, q_rope], -1)     # [B, L, Hq, r+rd]
        ckv = dense(p["w_dkv"], x)
        c, k_rope = ckv[..., :r], ckv[..., r:]
        k_rope = _rope_heads(k_rope[:, :, None, :], positions, cos, sin)
        lat = jnp.concatenate([c, k_rope[:, :, 0, :]], -1)
        kv = _scatter_span(cache["kv"], lat[:, :, None, :], off)
        cache = {"kv": kv, "len": off + adv}
        o = _xla_extend(q_eff.transpose(0, 2, 1, 3), kv, kv, off, l)
        y = jnp.einsum("bhlr,rhd->blhd", o[..., :r].transpose(0, 1, 2, 3),
                       p["w_uv"]["w"].reshape(r, hq, hd)) if False else             jnp.einsum("bhlr,rhd->bhld", o[..., :r],
                       p["w_uv"]["w"].reshape(r, hq, hd))
        y = y.transpose(0, 2, 1, 3).reshape(b, l, -1)
        return dense(p["wo"], y), cache

    q = dense(p["wq"], x).reshape(b, l, hq, hd)
    k = dense(p["wk"], x).reshape(b, l, hkv, hd)
    v = dense(p["wv"], x).reshape(b, l, hkv, hd)
    q = _rope_heads(q, positions, cos, sin).transpose(0, 2, 1, 3)
    k = _rope_heads(k, positions, cos, sin)
    kc = _scatter_span(cache["k"], k, off)
    vc = _scatter_span(cache["v"], v, off)
    cache = {"k": kc, "v": vc, "len": off + adv}
    o = _xla_extend(q, kc, vc, off, l)                   # [B, Hq, L, hd]
    y = o.transpose(0, 2, 1, 3).reshape(b, l, -1)
    return dense(p["wo"], y), cache


def _scatter_span(cache, new, off):
    """cache: [B, S, H, D]; new: [B, L, H, D]; off: [B] write offsets."""
    b, l = new.shape[0], new.shape[1]
    idx = off[:, None] + jnp.arange(l)[None, :]          # [B, L]
    bidx = jnp.arange(b)[:, None]
    return cache.at[bidx, idx].set(new.astype(cache.dtype))


def _xla_extend(q, k_cache, v_cache, off, l):
    """q: [B, Hq, L, D]; caches [B, S, Hkv, D]; causal over off+self.
    Grouped-head einsums (no KV repeat)."""
    b, hq, _, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, l, d)
    logits = jnp.einsum("bgrld,bsgd->bgrls", qg,
                        k_cache).astype(jnp.float32) / np.sqrt(d)
    qpos = off[:, None, None, None, None] \
        + jnp.arange(l)[None, None, None, :, None]
    kpos = jnp.arange(s)[None, None, None, None, :]
    logits = jnp.where(kpos <= qpos, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bgrls,bsgd->bgrld", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, hq, l, d)


def _quantize_kv(x):
    """x: [B, L, H, D] -> (int8 values, f32 scales [B, L, H])."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                        1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(cache, key):
    c = cache[key]
    if c.dtype != jnp.int8:
        return c
    return c.astype(jnp.float32) * cache[key + "_scale"][..., None]


def init_attn_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    from ..tuning import cache_quant
    if cache_quant():
        dtype = jnp.int8
    ln = {"len": jnp.zeros((batch,), jnp.int32)}
    if cfg.attn_kind == "mla":
        width = cfg.mla_kv_rank + cfg.mla_rope_dim
        out = {"kv": jnp.zeros((batch, max_len, 1, width), dtype), **ln}
        if dtype == jnp.int8:
            out["kv_scale"] = jnp.zeros((batch, max_len, 1), jnp.float32)
        return out
    out = {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
           "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
           **ln}
    if dtype == jnp.int8:
        out["k_scale"] = jnp.zeros((batch, max_len, cfg.n_kv_heads), jnp.float32)
        out["v_scale"] = jnp.zeros((batch, max_len, cfg.n_kv_heads), jnp.float32)
    return out
