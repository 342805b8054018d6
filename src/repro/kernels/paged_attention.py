"""Paged GQA decode attention: one new token per request, read in place from
a KV block pool through the request's block table.

The pools are ``[num_blocks, block_len, Hkv * D]``: one block holds every
KV head of ``block_len`` consecutive positions, lane-dense. Block ids
(``tables``, flattened) and live lengths (``lens``) arrive as scalar-prefetch
operands in SMEM. Grid = (batch,); each request walks its live blocks only,
``block_steps`` blocks a step, with two VMEM buffers so that the next step's
block DMAs run under this step's compute, and an online softmax across
steps. Blocks past ``ceil(len / block_len)`` are neither fetched nor
computed.

All heads of a request are served by one fetch. The query enters
block-diagonal: row ``h`` holds ``q[h]`` in the lanes of its KV head
``h // rep`` and zeros elsewhere, so the scores of every head are one
``[Hq, C] x [C, positions]`` product and the values one
``[Hq, positions] x [positions, C]`` product. Row ``h`` of the output is
right in its own head's lanes; the wrapper keeps those. Products run at
float32 (``precision=HIGHEST``), as the serving configuration states.
The block-diagonal form does ``Hkv`` times the useful MXU work, against KV
bytes that grow only as ``Hkv``: it suits a model while that work stays
under the blocks' DMA time, and a model with many more KV heads wants a
per-head product instead.

Validated against ``_xla_decode`` over ``gather_paged_cache`` of the same
pool (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
STEP_POSITIONS = 128     # positions per step: block_steps * block_len
_HIGHEST = jax.lax.Precision.HIGHEST


def _call(*fns) -> None:
    for f in fns:
        f()


def _kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem, m_ref, l_ref, acc_ref,
            *, block_steps: int, n_tables: int, scale: float):
    b = pl.program_id(0)
    bl = k_hbm.shape[1]
    length = lens_ref[b]
    n_live = (length + bl - 1) // bl                    # blocks to read
    n_steps = (n_live + block_steps - 1) // block_steps

    @pl.when(b == 0)
    def _zero_buffers():
        # a step's blocks past the live ones are never fetched: what the
        # buffer holds there (masked off below) must at least be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    def copies(step, slot):
        out = []
        for p in range(block_steps):
            j = step * block_steps + p
            blk = tables_ref[b * n_tables + jnp.minimum(j, n_tables - 1)]
            rows = pl.ds(p * bl, bl)
            out.append((j < n_live,
                        pltpu.make_async_copy(k_hbm.at[blk],
                                              k_buf.at[slot, rows],
                                              sem.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[blk],
                                              v_buf.at[slot, rows],
                                              sem.at[1, slot])))
        return out

    def start(step, slot):
        for live, ck, cv in copies(step, slot):
            pl.when(live)(functools.partial(_call, ck.start, cv.start))

    def wait(step, slot):
        for live, ck, cv in copies(step, slot):
            pl.when(live)(functools.partial(_call, ck.wait, cv.wait))

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0].astype(jnp.float32)                    # [Hq, C]
    start(0, 0)

    def body(step, carry):
        slot = step % 2

        @pl.when(step + 1 < n_steps)
        def _prefetch():
            start(step + 1, 1 - slot)

        wait(step, slot)
        k = k_buf[slot].astype(jnp.float32)             # [P * bl, C]
        v = v_buf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32) * scale  # [Hq, P * bl]
        pos = step * (block_steps * bl) \
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < length
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)         # [Hq, C]
        m_ref[...] = m_cur
        return carry

    jax.lax.fori_loop(0, n_steps, body, 0)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,        # [B, Hq, D]
    k_pool: jax.Array,   # [num_blocks, block_len, Hkv * D]
    v_pool: jax.Array,   # [num_blocks, block_len, Hkv * D]
    tables: jax.Array,   # [B, T] int32 block ids
    lengths: jax.Array,  # [B] int32 positions to attend (>= 1)
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    _, bl, c = k_pool.shape
    hkv = c // d
    assert hkv * d == c and hq % hkv == 0
    rep = hq // hkv
    n_tables = tables.shape[1]
    # blocks a step: about STEP_POSITIONS positions, at most the table
    steps = max(1, min(n_tables, STEP_POSITIONS // bl))
    # block-diagonal query: row h carries q[h] in the lanes of head h // rep
    own = (jnp.arange(hq)[:, None] // rep) == jnp.arange(hkv)[None, :]
    q_bd = jnp.where(own[None, :, :, None], q[:, :, None, :], 0.0)
    q_bd = q_bd.reshape(b, hq, c).astype(jnp.float32)

    kernel = functools.partial(_kernel, block_steps=steps, n_tables=n_tables,
                               scale=float(1.0 / (d ** 0.5)))
    row = pl.BlockSpec((1, hq, c), lambda bi, *_: (bi, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, steps * bl, c), k_pool.dtype),
                pltpu.VMEM((2, steps * bl, c), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, c), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hq, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q_bd, k_pool, v_pool)
    # keep each row's own head: out[b, h, h // rep]
    out = out.reshape(b, hkv, rep, hkv, d)
    g = jnp.arange(hkv)
    return out[:, g, :, g, :].transpose(1, 0, 2, 3).reshape(b, hq, d) \
        .astype(q.dtype)
