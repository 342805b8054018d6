"""Population-parallel mapping-evaluation kernel (the paper's own hot loop).

The GA evaluates 100+ mappings per generation; each evaluation is a
sequential timing recurrence over the scheduled op order:

    start_t = max(chip_free[chip_t], max_w end[ppos[t, w]])
    end[t] = chip_free[chip_t] = start_t + t_proc[t]

where ``ppos`` is the *padded predecessor-position* layout shared with the
dense XLA path (``repro.core.jax_evaluator._structural_pass``): for every
scheduled step t, the positions of its (<= W) predecessor ops in the same
scheduled order, padded with the sentinel T, which indexes the
permanently-zero slot of the end vector (matching the oracle's
``max(..., 0)``).

The recurrence is tiny but strictly sequential in t, and every access is a
scalar at a data-dependent index — work for the TPU's scalar unit, not its
vector unit. So the whole kernel lives in SMEM: one grid step per
(batch, individual) member, whose index rows, cost row and output rows
are SMEM blocks, with the ``end`` state in an SMEM scratch vector and the
per-chiplet free times kept in the output block itself. Mosaic refuses
dynamic lane-axis loads and stores on VMEM, so a VMEM layout does not
compile. Every operand is reshaped so that a block's last two dimensions
equal the array's (``(1, T)`` rows of a ``(P, 1, T)`` array), the layout
rule of Pallas TPU blocks.

SMEM is small (1 MiB per core on v5e) and every block is double-buffered,
so the schedule length T is capped: :func:`smem_bytes` prices a call and
the wrappers raise above :data:`SMEM_BUDGET_BYTES` rather than fall back
to another backend.

The outputs are the full timing matrix — per-op end times in scheduled
order plus per-chiplet free times — which ``repro.core.timing`` folds into
per-request TTFT/TPOT for the SLO-aware GA objectives.

Validated against ``ref.mapping_eval_reference`` (and transitively against
the numpy evaluation engine, whose timing pass has identical semantics).

``mapping_eval_fused`` additionally fuses pass A — the gather that
assembles per-step processing times from the un-gathered per-(batch,
individual) cost row ``t_proc[l]`` via the schedule's flattened layer
index ``sched_idx[t]`` — into the same program, so the (B, P, T)
``tproc_sched`` tensor is never materialised between the cost pass and
the recurrence. The grid order is tunable (``batch_major`` keeps one
individual's index blocks resident across the inner batch sweep;
``pop_major`` streams individuals fastest) and picked by a small timed
probe cached per shape when running compiled on TPU.
"""
from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GRID_ORDERS = ("batch_major", "pop_major")
_GRID_ORDER_ENV = "REPRO_FUSED_GRID_ORDER"
_AUTOTUNE_CACHE: dict[tuple, str] = {}

# SMEM bytes one call may use: v5e's 1 MiB per core less 16 KiB for what
# Mosaic adds to :func:`smem_bytes` (6-9 KiB when compiled for a described
# v5e chip). At W=8 this caps the fused kernel at T=10320.
SMEM_BUDGET_BYTES = 1024 * 1024 - 16 * 1024


def smem_bytes(t_len: int, width: int, n_flat: int, n_chips: int,
               fused: bool) -> int:
    """SMEM bytes of one kernel call: the double-buffered int32 index rows
    (chip, ppos and, fused, sched), the f32 cost row and output rows, and
    the (T+1,) end scratch."""
    words = t_len * (1 + width + (1 if fused else 0)) + n_flat \
        + t_len + n_chips
    return 4 * (2 * words + t_len + 1)


def _check_smem(t_len, width, n_flat, n_chips, fused):
    need = smem_bytes(t_len, width, n_flat, n_chips, fused)
    if need > SMEM_BUDGET_BYTES:
        kind = "mapping_eval_fused" if fused else "mapping_eval"
        raise ValueError(
            f"{kind}: schedule length T={t_len} (W={width}, L={n_flat}, "
            f"C={n_chips}) needs {need} bytes of SMEM, over the "
            f"{SMEM_BUDGET_BYTES}-byte cap of the SMEM layout; use the "
            "'dense' timing backend for schedules this long")


def _recurrence(tproc_at, chip_ref, ppos_ref, end_ref, free_ref, end_scr,
                *, t_len: int, width: int, n_chips: int):
    """The pass-B recurrence of one (batch, individual) member, all scalar
    SMEM accesses. ``tproc_at(t)`` yields step t's processing time."""
    def zero(i, _):
        end_scr[i] = jnp.float32(0.0)           # slot T stays 0 (sentinel)
        return 0

    jax.lax.fori_loop(0, t_len + 1, zero, 0)
    for c in range(n_chips):
        free_ref[0, c] = jnp.float32(0.0)

    def step(t, _):
        c = chip_ref[0, t]
        pred_end = jnp.float32(0.0)
        for w in range(width):                  # static unroll; W is small
            pred_end = jnp.maximum(pred_end,
                                   end_scr[ppos_ref[0, t * width + w]])
        start = jnp.maximum(free_ref[0, c], pred_end)
        fin = start + tproc_at(t)
        end_scr[t] = fin
        end_ref[0, t] = fin
        free_ref[0, c] = fin
        return 0

    jax.lax.fori_loop(0, t_len, step, 0)


def _mapping_eval_kernel(chip_ref, ppos_ref, tproc_ref, end_ref, free_ref,
                         end_scr, *, t_len: int, width: int, n_chips: int):
    _recurrence(lambda t: tproc_ref[0, t], chip_ref, ppos_ref, end_ref,
                free_ref, end_scr, t_len=t_len, width=width,
                n_chips=n_chips)


def _smem_row(n: int, index_map) -> pl.BlockSpec:
    """One squeezed (1, n) row of a (..., 1, n) array, held in SMEM."""
    return pl.BlockSpec((None, 1, n), index_map, memory_space=pltpu.SMEM)


def _smem_cell_row(n: int, index_map) -> pl.BlockSpec:
    """One (1, n) row of a (B, P, 1, n) array, held in SMEM."""
    return pl.BlockSpec((None, None, 1, n), index_map,
                        memory_space=pltpu.SMEM)


def _out_shapes(n_batch, pop, t_len, n_chips):
    return [jax.ShapeDtypeStruct((n_batch, pop, 1, t_len), jnp.float32),
            jax.ShapeDtypeStruct((n_batch, pop, 1, n_chips), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("n_chips", "interpret"))
def mapping_eval(
    t_proc: jax.Array,   # [B, P, T] float32 per-op processing times
    chip: jax.Array,     # [P, T] int32 chiplet per scheduled op
    ppos: jax.Array,     # [P, T, W] int32 padded predecessor positions
    n_chips: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Full timing matrix per (batch, population) member:
    (end [B, P, T] scheduled-order op end times, free [B, P, C] per-chiplet
    free times). The makespan is ``end.max(-1)``."""
    n_batch, pop, t_len = t_proc.shape
    width = ppos.shape[-1]
    _check_smem(t_len, width, t_len, n_chips, fused=False)
    kernel = functools.partial(_mapping_eval_kernel, t_len=t_len,
                               width=width, n_chips=n_chips)
    idx = lambda p, b: (p, 0, 0)                       # noqa: E731
    cell = lambda p, b: (b, p, 0, 0)                   # noqa: E731
    end, free = pl.pallas_call(
        kernel,
        grid=(pop, n_batch),
        in_specs=[_smem_row(t_len, idx),
                  _smem_row(t_len * width, idx),
                  _smem_cell_row(t_len, cell)],
        out_specs=[_smem_cell_row(t_len, cell),
                   _smem_cell_row(n_chips, cell)],
        out_shape=_out_shapes(n_batch, pop, t_len, n_chips),
        scratch_shapes=[pltpu.SMEM((t_len + 1,), jnp.float32)],
        interpret=interpret,
        name="mapping_eval",
    )(chip.astype(jnp.int32).reshape(pop, 1, t_len),
      ppos.astype(jnp.int32).reshape(pop, 1, t_len * width),
      t_proc.astype(jnp.float32).reshape(n_batch, pop, 1, t_len))
    return end[:, :, 0], free[:, :, 0]


# --------------------------------------------------------------------------
# Fused pass-A + pass-B megakernel
# --------------------------------------------------------------------------


def _mapping_eval_fused_kernel(sched_ref, chip_ref, ppos_ref, tproc_ref,
                               end_ref, free_ref, end_scr, *,
                               t_len: int, width: int, n_chips: int):
    """One (individual, batch) grid cell: gather each step's processing
    time from the un-gathered cost row (pass A) and run the sequential
    end/free recurrence (pass B), all from SMEM-resident state."""
    _recurrence(lambda t: tproc_ref[0, sched_ref[0, t]], chip_ref, ppos_ref,
                end_ref, free_ref, end_scr, t_len=t_len, width=width,
                n_chips=n_chips)


@functools.partial(jax.jit,
                   static_argnames=("n_chips", "grid_order", "interpret"))
def _mapping_eval_fused_call(t_proc, sched_idx, chip, ppos, n_chips,
                             grid_order, interpret):
    n_batch, pop, n_flat = t_proc.shape
    t_len = chip.shape[-1]
    width = ppos.shape[-1]
    _check_smem(t_len, width, n_flat, n_chips, fused=True)
    kernel = functools.partial(_mapping_eval_fused_kernel, t_len=t_len,
                               width=width, n_chips=n_chips)
    # batch_major: the batch axis is innermost, so an individual's index
    # blocks (sched/chip/ppos, index constant in b) stay resident across
    # its whole batch sweep and only the cost row streams — the pipeline
    # double-buffers it one grid step ahead.
    # pop_major: the population axis is innermost; every grid step streams
    # a new individual's index blocks against a resident batch.
    if grid_order == "batch_major":
        grid = (pop, n_batch)
        idx = lambda p, b: (p, 0, 0)                   # noqa: E731
        cell = lambda p, b: (b, p, 0, 0)               # noqa: E731
    elif grid_order == "pop_major":
        grid = (n_batch, pop)
        idx = lambda b, p: (p, 0, 0)                   # noqa: E731
        cell = lambda b, p: (b, p, 0, 0)               # noqa: E731
    else:
        raise ValueError(f"unknown grid order {grid_order!r}; "
                         f"choose from {GRID_ORDERS}")
    end, free = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_smem_row(t_len, idx),
                  _smem_row(t_len, idx),
                  _smem_row(t_len * width, idx),
                  _smem_cell_row(n_flat, cell)],
        out_specs=[_smem_cell_row(t_len, cell),
                   _smem_cell_row(n_chips, cell)],
        out_shape=_out_shapes(n_batch, pop, t_len, n_chips),
        scratch_shapes=[pltpu.SMEM((t_len + 1,), jnp.float32)],
        interpret=interpret,
        name="mapping_eval_fused",
    )(sched_idx.astype(jnp.int32).reshape(pop, 1, t_len),
      chip.astype(jnp.int32).reshape(pop, 1, t_len),
      ppos.astype(jnp.int32).reshape(pop, 1, t_len * width),
      t_proc.astype(jnp.float32).reshape(n_batch, pop, 1, n_flat))
    return end[:, :, 0], free[:, :, 0]


def default_grid_order() -> str:
    """The grid order used when none is given and no probe can run:
    the ``REPRO_FUSED_GRID_ORDER`` environment variable, else
    ``batch_major`` (index tensors resident across the batch sweep)."""
    order = os.environ.get(_GRID_ORDER_ENV, "batch_major")
    if order not in GRID_ORDERS:
        raise ValueError(f"{_GRID_ORDER_ENV}={order!r}; "
                         f"choose from {GRID_ORDERS}")
    return order


def autotune_grid_order(t_proc, sched_idx, chip, ppos, n_chips,
                        interpret: bool = False) -> str:
    """Pick the faster grid order for this shape by timing both compiled
    variants once, cached per (B, P, T, W, C, L) shape. Interpret mode
    never probes (the interpreter's walltime is meaningless) and an
    explicit ``REPRO_FUSED_GRID_ORDER`` always wins."""
    if os.environ.get(_GRID_ORDER_ENV):
        return default_grid_order()
    if interpret or jax.default_backend() != "tpu":
        return default_grid_order()
    key = (t_proc.shape, chip.shape[-1], ppos.shape[-1], n_chips)
    hit = _AUTOTUNE_CACHE.get(key)
    if hit is not None:
        return hit
    timings = {}
    for order in GRID_ORDERS:
        out = _mapping_eval_fused_call(t_proc, sched_idx, chip, ppos,
                                       n_chips, order, False)
        jax.block_until_ready(out)             # compile + warm
        t0 = time.perf_counter()
        out = _mapping_eval_fused_call(t_proc, sched_idx, chip, ppos,
                                       n_chips, order, False)
        jax.block_until_ready(out)
        timings[order] = time.perf_counter() - t0
    best = min(timings, key=timings.get)
    _AUTOTUNE_CACHE[key] = best
    return best


def mapping_eval_fused(
    t_proc: jax.Array,     # [B, P, L] un-gathered per-individual cost rows
    sched_idx: jax.Array,  # [P, T] int32 flattened layer index per step
    chip: jax.Array,       # [P, T] int32 chiplet per scheduled op
    ppos: jax.Array,       # [P, T, W] int32 padded predecessor positions
    n_chips: int,
    grid_order: str | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused pass-A/pass-B timing matrix per (batch, population) member:
    (end [B, P, T], free [B, P, C]). ``t_proc`` is the UN-gathered
    (rows * M)-flat cost row; the kernel gathers step t's processing time
    as ``t_proc[sched_idx[t]]`` in SMEM, so ``tproc_sched`` never exists
    as a device tensor. ``grid_order=None`` asks the autotune probe (TPU
    compiled runs only; falls back to :func:`default_grid_order`)."""
    if grid_order is None:
        if isinstance(t_proc, jax.core.Tracer):
            grid_order = default_grid_order()   # inside jit: no probe
        else:
            grid_order = autotune_grid_order(t_proc, sched_idx, chip, ppos,
                                             n_chips, interpret=interpret)
    return _mapping_eval_fused_call(t_proc, sched_idx, chip, ppos, n_chips,
                                    grid_order, interpret)


@functools.partial(jax.jit, static_argnames=("n_chips",))
def mapping_eval_fused_host(
    t_proc: jax.Array,     # [B, P, L] un-gathered per-individual cost rows
    sched_idx: jax.Array,  # [P, T] int32
    chip: jax.Array,       # [P, T] int32
    ppos: jax.Array,       # [P, T, W] int32
    n_chips: int,
) -> tuple[jax.Array, jax.Array]:
    """Off-TPU execution of the fused contract: the pass-A gather and the
    batched ``lax.scan`` recurrence fused into ONE jitted program (no
    host round-trip between passes). Bitwise-identical to gathering
    ``tproc_sched`` and running the dense backend — the gather is exact
    and the per-step float ops are issued in the same order."""
    from ..core.timing import dense_pass_b

    n_batch, pop, _ = t_proc.shape
    t_len = chip.shape[-1]
    idx = jnp.broadcast_to(sched_idx[None].astype(jnp.int32),
                           (n_batch, pop, t_len))
    tproc_sched = jnp.take_along_axis(t_proc.astype(jnp.float32), idx, -1)
    per_p = jax.vmap(lambda tp, c, pp: dense_pass_b(tp, c, pp, n_chips))
    return jax.vmap(lambda tp: per_p(tp, chip, ppos))(tproc_sched)
