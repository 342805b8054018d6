"""Public jit'd wrappers for the Pallas kernels.

Off-TPU the kernels run in interpret mode — the kernel body executes
eagerly in Python, validating the exact TPU code path. On a TPU backend
they compile to Mosaic. ``use_interpret()`` picks from the backend.
"""
from __future__ import annotations

import jax

from .decode_attention import decode_attention as _decode_attention
from .flash_attention import flash_attention as _flash_attention
from .mapping_eval import mapping_eval as _mapping_eval
from .mapping_eval import mapping_eval_fused as _mapping_eval_fused
from .mapping_eval import mapping_eval_fused_host
from .ssd_scan import ssd_scan as _ssd_scan


def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, causal=True, scale=None, block_q=128,
                    block_k=128, interpret=None):
    return _flash_attention(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=use_interpret() if interpret is None else interpret)


def decode_attention(q, k_cache, v_cache, lengths, scale=None, block_s=512,
                     interpret=None):
    return _decode_attention(
        q, k_cache, v_cache, lengths, scale=scale, block_s=block_s,
        interpret=use_interpret() if interpret is None else interpret)


def ssd_scan(x, dt, a, b_mat, c_mat, chunk=128, interpret=None):
    return _ssd_scan(
        x, dt, a, b_mat, c_mat, chunk=chunk,
        interpret=use_interpret() if interpret is None else interpret)


def mapping_eval(t_proc, chip, ppos, n_chips, interpret=None):
    return _mapping_eval(
        t_proc, chip, ppos, n_chips,
        interpret=use_interpret() if interpret is None else interpret)


def mapping_eval_fused(t_proc, sched_idx, chip, ppos, n_chips,
                       grid_order=None, interpret=None):
    """Fused pass-A/pass-B megakernel: ``t_proc`` is the UN-gathered
    (B, P, rows*M) cost rows, gathered in-kernel via ``sched_idx``."""
    return _mapping_eval_fused(
        t_proc, sched_idx, chip, ppos, n_chips, grid_order=grid_order,
        interpret=use_interpret() if interpret is None else interpret)
