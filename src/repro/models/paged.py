"""Block-table-indexed serving paths over a paged KV block pool.

The dense serving cache is one ``[max_batch, max_len, ...]`` array per
layer; a request owns a whole row whether it uses 3 tokens of it or all of
them. The paged layout replaces the row with a *block pool*
``[num_blocks, block_len, ...]`` plus a per-request *block table* — the
vLLM/SHARK residency model — so the memory a request pins is proportional
to its context, and "can we admit one more warm decode" becomes a
free-list question instead of an assumption.

Index conventions (shared with ``repro.serving.paged_cache``):

* block 0 is the reserved **null block**: block tables are padded with it,
  and any write that falls outside a request's allocated span is routed to
  it. Its contents are garbage by design — every attention path masks by
  ``len``, so garbage past the live context is never read (same invariant
  that lets the dense engine skip zero-on-admit).
* mamba / conv recurrent state has no sequence axis, so it stays
  slot-indexed: arrays carry ``max_batch + 1`` rows and the extra last row
  is the **scratch slot** used by batch-padding lanes.

* float MHA/GQA attention pools (``k``/``v``) are lane-dense
  ``[num_blocks, block_len, Hkv * D]``: one block row holds every KV head of
  a position, the layout the paged decode kernel DMAs whole. Every other
  pool (MLA latent ``kv``, int8 ``k``/``v`` with ``*_scale``) keeps the
  dense cache's trailing dims. Only this module and the kernel read the
  layout.

The decode step reads float attention pools in place: it writes the new
position through the block table, then ``kernels/paged_attention.py``
attends over each request's live blocks alone. Every other layer kind, and
the prefill path, *gathers* a request batch's blocks into the dense layout,
runs the unmodified ``decode_step`` / ``extend`` model functions, and
scatters the touched positions back through the block table — bit-identical
in its unmasked reads to the dense engine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels import ops
from .attention import attention_decode, decode_qkv, init_attn_cache
from .layers import dense
from .mamba2 import init_mamba_cache
from .transformer import ModelConfig, decode_step, extend

NULL_BLOCK = 0


def is_slot_layer(layer: dict) -> bool:
    """Recurrent (mamba) layers keep per-slot state; attention layers page."""
    return "state" in layer


def reads_in_place(layer: dict) -> bool:
    """Float MHA/GQA attention pools: the decode kernel reads them through
    the block table. MLA latents, int8 pools and slot state are gathered."""
    return set(layer) == {"k", "v"} and \
        jnp.issubdtype(layer["k"].dtype, jnp.floating)


def init_paged_pools(cfg: ModelConfig, max_batch: int, num_blocks: int,
                     block_len: int, dtype=jnp.float32):
    """Per-layer pools: attention layers get ``[num_blocks, block_len, ...]``
    KV pools (reusing the dense cache constructor with the pool shape, float
    ``k``/``v`` with their heads flattened into lanes); recurrent layers get
    slot state with one extra scratch row."""
    pools = []
    for i in range(cfg.n_layers):
        if cfg.mixer_kind(i) == "attn":
            c = init_attn_cache(cfg, num_blocks, block_len, dtype)
        else:
            c = init_mamba_cache(cfg, max_batch + 1)
        c.pop("len")            # lengths live host-side, per slot
        if reads_in_place(c):
            c = {k: v.reshape(num_blocks, block_len, -1) for k, v in c.items()}
        pools.append(c)
    return pools


def _gather_layer(layer, tables, lens, slots, cfg: ModelConfig):
    n, t = tables.shape
    if is_slot_layer(layer):
        d = {k: v[slots] for k, v in layer.items()}
    else:
        heads = (cfg.n_kv_heads, cfg.head_dim) if reads_in_place(layer) \
            else None
        d = {}
        for k, pool in layer.items():
            g = pool[tables]                           # [N, T, bl, ...]
            d[k] = g.reshape((n, t * pool.shape[1]) + (heads or pool.shape[2:]))
    d["len"] = lens
    return d


def gather_paged_cache(pools, tables, lens, slots, cfg: ModelConfig):
    """Assemble the dense per-request cache view a model function expects.

    ``tables``: [N, T] int32 block ids; ``lens``: [N] live context lengths;
    ``slots``: [N] slot ids for the recurrent state rows; ``cfg`` splits the
    lanes of float ``k``/``v`` pools back into ``(n_kv_heads, head_dim)``.
    Returns a cache list in the dense engine layout ([N, T*block_len, ...]
    per attention layer) — positions past ``lens`` hold whatever the
    referenced blocks hold (the null block included) and rely on length
    masking downstream.
    """
    return [_gather_layer(layer, tables, lens, slots, cfg) for layer in pools]


def paged_decode(params, cfg: ModelConfig, tokens, pools, tables, lens,
                 slots, block_len: int, impl: str = "xla"):
    """One decode step for a batch of paged requests.

    Writes each request's new KV position through its block table (position
    ``lens[j]`` lands in block ``tables[j, lens[j] // block_len]``). Float
    attention layers then attend in place over the pool, ``lens + 1``
    positions through the table (``ops.paged_decode_attention``); every
    other layer runs the stock decode over the gathered dense view, and its
    one written position is scattered back. Padding lanes must use the null
    block table and the scratch slot so their writes are sunk.
    Returns ``(argmax tokens [N], new pools)``.
    """
    n = tokens.shape[0]
    bidx = jnp.take_along_axis(tables, (lens // block_len)[:, None],
                               axis=1)[:, 0]           # [N] target blocks
    off = lens % block_len
    cache = [layer if reads_in_place(layer)
             else _gather_layer(layer, tables, lens, slots, cfg)
             for layer in pools]

    def attend(i, p, x, rope, c):
        if not reads_in_place(pools[i]):
            return attention_decode(p, x, cfg, rope, c, impl=impl)
        q, k, v = decode_qkv(p, x[:, 0], cfg, rope, lens)
        new = {name: c[name].at[bidx, off].set(
                   val.reshape(n, -1).astype(c[name].dtype))
               for name, val in (("k", k), ("v", v))}
        o = ops.paged_decode_attention(q, new["k"], new["v"], tables, lens + 1)
        return dense(p["wo"], o.astype(x.dtype).reshape(n, -1))[:, None], new

    logits, new_cache = decode_step(params, cfg, tokens, cache, impl=impl,
                                    attend=attend)
    new_pools = []
    for layer, new in zip(pools, new_cache):
        if reads_in_place(layer):
            new_pools.append(new)
            continue
        if is_slot_layer(layer):
            new_pools.append(
                {k: layer[k].at[slots].set(new[k]) for k in layer})
            continue
        d = {}
        for k, pool in layer.items():
            arr = new[k]                               # dense [N, S, ...]
            idx = lens.reshape((n,) + (1,) * (arr.ndim - 1))
            upd = jnp.take_along_axis(arr, idx, axis=1)[:, 0]
            d[k] = pool.at[bidx, off].set(upd.astype(pool.dtype))
        new_pools.append(d)
    return jnp.argmax(logits, -1), new_pools


def paged_extend(params, cfg: ModelConfig, tokens, pools, table, off, slot,
                 length, block_len: int, impl: str = "xla"):
    """One (possibly chunked/padded) prefill chunk for a single request.

    ``tokens``: [C] right-padded chunk; ``table``: [T] the request's block
    table; ``off``: current context length (write offset); ``length``: true
    chunk length. Runs the stock ``extend`` over the gathered dense row,
    then scatters back the whole-block window covering [off, off+C) — the
    blocks are request-owned so rewriting untouched leading/trailing
    positions in the window is a no-op, and window blocks past the table
    (or past the allocated span) are routed to the null block.
    Returns ``(argmax token, new pools)``.
    """
    c = tokens.shape[0]
    t = table.shape[0]
    w = (c + block_len - 1) // block_len + 1           # window, static
    lens1 = jnp.reshape(off, (1,))
    slots1 = jnp.reshape(slot, (1,))
    cache = gather_paged_cache(pools, table[None], lens1, slots1, cfg)
    logits, new_cache = extend(params, cfg, tokens[None], cache, impl=impl,
                               length=length)
    w0 = off // block_len
    widx = w0 + jnp.arange(w)
    safe = jnp.where(widx < t, table[jnp.minimum(widx, t - 1)], NULL_BLOCK)
    new_pools = []
    for layer, new in zip(pools, new_cache):
        if is_slot_layer(layer):
            new_pools.append(
                {k: layer[k].at[slot].set(new[k][0]) for k in layer})
            continue
        d = {}
        for k, pool in layer.items():
            row = new[k][0]                            # [S, ...]
            pad = [(0, w * block_len)] + [(0, 0)] * (row.ndim - 1)
            row = jnp.pad(row, pad)
            win = jax.lax.dynamic_slice_in_dim(row, w0 * block_len,
                                               w * block_len, axis=0)
            win = win.reshape((w, block_len) + pool.shape[2:])
            d[k] = pool.at[safe].set(win.astype(pool.dtype))
        new_pools.append(d)
    return jnp.argmax(logits, -1)[0], new_pools
