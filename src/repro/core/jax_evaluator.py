"""JAX population-parallel evaluation engine.

The paper reports ~3 minutes per mapping search on a 128-core server — the
GA's evaluation loop is the DSE hot spot. Here the whole population is
evaluated in one jitted call, structured as:

* **structural pass** (per individual, shared by every batch of a group):
  Algorithm 2's sequential chip-status scan re-expressed densely — the
  status table "last (row, col) executed on chip c before step t" is a
  prefix-max over the schedule, so weight-residency / liveness / write-out
  flags become pure gathers with no sequential dependency;
* **cost contraction** (per batch x individual): the padded predecessor
  liveness masks contract with the per-batch byte tables into NoP/DRAM
  traffic, per-op ``T_proc`` and energy;
* **timing pass B** (per batch x individual): the only truly sequential
  part — the makespan recurrence — delegated to a pluggable
  :mod:`repro.core.timing` backend: ``dense`` (batched ``lax.scan``, the
  XLA default), ``pallas`` (``repro.kernels.mapping_eval``, the
  SMEM-resident TPU kernel over a (population, batches) grid; interpreted
  on CPU when asked), or ``fused`` (``repro.kernels.mapping_eval_fused``,
  the pass-A + pass-B megakernel: the per-step ``T_proc`` gather happens
  *inside* the kernel via the structural pass's ``sched_idx``, so the
  (B, P, T) ``tproc_sched`` tensor is never materialised in HBM; off-TPU
  and un-interpreted it routes to the fused single-program XLA path,
  counted as a ``fused->host`` reroute in ``timing_backend_stats()``).
  All consume the same padded predecessor-position layout the structural
  pass emits, and all return the full timing matrix (per-op end times +
  per-chiplet free times), which ``GroupPopulationEvaluator`` folds into
  per-request timings for the SLO-aware GA objectives.

Semantics match ``evaluator.evaluate`` exactly (tested to 1e-6).

Two entry points share this body: ``PopulationEvaluator`` (one graph) and
``GroupPopulationEvaluator`` (all structurally-identical batches of a
``search_mapping`` group vmapped on a leading batch axis — a whole GA
generation is ONE jitted call). Both are module-level ``jax.jit`` functions,
so the compile cache is keyed on shapes only: repeated BO iterations with
the same (rows, M, C) never recompile. Scheduled orders come from
``encoding.ScheduledOrderCache`` — per-individual Python loops never run
when the segmentation is unchanged. Per-batch cost tables are uploaded
once per distinct table set (module-level keyed cache) and the device
buffers persist across GA generations AND across ``search_mapping`` calls
on the same scenario.

**Multi-device sharding.** Every per-individual quantity is independent
along the population axis (the whole pipeline above is a vmap), so the
evaluators scale out as pure data parallelism: ``devices=`` (``None`` =
all local devices, an int, a device list, or a 1-D ``jax.sharding.Mesh``)
shards the population over a ``("pop",)`` mesh via ``jit(shard_map(...))``
— each device runs the identical vmapped program on its population shard,
so per-individual results are *bit-identical* to the single-device path.
Populations are padded to a multiple of the device count (padding rows are
sliced off the outputs) and the stacked cost-table buffers are replicated
once per mesh device through the same persistent cache, keyed on a device
signature. On one default device the evaluators take the exact legacy
code path.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import telemetry
from .encoding import MappingEncoding, ScheduledOrderCache, as_stacked
from .evaluator import CostTables
from .hardware import (
    DATAFLOWS,
    E_DRAM_PJ_PER_BYTE,
    E_NOP_PJ_PER_BYTE_HOP,
    HardwareConfig,
)
from ..kernels.mapping_eval import default_grid_order
from .timing import (
    FusedTimingBackend,
    OracleTimingBackend,
    PallasTimingBackend,
    TimingBackend,
    TimingMatrix,
    attribute_group_violations,
    dense_pass_b,
    fold_request_timings,
    padded_predecessor_columns,
    record_backend_dispatch,
    record_backend_fallback,
    resolve_timing_backend,
)
from .workload import ExecutionGraph

available = True


def _structural_pass(order, lc, n_succ, hops, pred_cols, pred_valid,
                     n_chips: int):
    """Mapping-only quantities for one individual: Algorithm-2 flags as
    dense gathers plus the schedule-order index tensors the timing pass
    needs. Predecessors are contiguous column intervals of width <= W, so
    everything stays on narrow (rows, M, W) tensors indexed by
    ``pred_cols`` instead of dense (rows, M, M). Returns a dict of arrays."""
    rows, m_cols = lc.shape
    T = order.shape[0]
    b_seq, l_seq = order[:, 0], order[:, 1]
    chip_seq = lc[b_seq, l_seq]                           # (T,)
    t_ids = jnp.arange(T, dtype=jnp.int32)
    marked = jnp.where(chip_seq[:, None] == jnp.arange(n_chips)[None, :],
                       t_ids[:, None], -1)                # (T, C)
    last_incl = jax.lax.cummax(marked, axis=0)
    last_before = jnp.concatenate(                        # strictly < t
        [jnp.full((1, n_chips), -1, last_incl.dtype), last_incl[:-1]], 0)

    pos = jnp.zeros((rows, m_cols), jnp.int32) \
        .at[b_seq, l_seq].set(t_ids)                      # (rows, M)

    # liveness of producer column pc[l, w] for consumer (b, l): the last op
    # on the producer's chip strictly before the consumer is the producer
    cpw = lc[:, pred_cols]                                # (rows, M, W)
    ppos_mat = pos[:, pred_cols]                          # (rows, M, W)
    lbp = last_before[pos[:, :, None], cpw]               # (rows, M, W)
    live = (lbp == ppos_mat) & pred_valid[None, :, :]

    # weight residency: previous op on the consumer's chip ran the same
    # column for a different micro-batch
    prev_t = last_before[t_ids, chip_seq]                 # (T,)
    safe_prev = jnp.maximum(prev_t, 0)
    elide_t = (prev_t >= 0) & (l_seq[safe_prev] == l_seq) \
        & (b_seq[safe_prev] != b_seq)
    elide = jnp.zeros((rows, m_cols), jnp.bool_) \
        .at[b_seq, l_seq].set(elide_t)

    # traffic masks: live producers on another chip arrive over the NoP
    # (hop-weighted), dead ones are re-read from DRAM
    diff_chip = cpw != lc[:, :, None]
    nop_mask = (live & diff_chip).astype(jnp.float32)
    hop_mask = nop_mask * hops[cpw, lc[:, :, None]]
    dram_mask = (pred_valid[None, :, :] & ~live).astype(jnp.float32)

    # write-out elision: every successor consumed the output live
    consumed = jnp.zeros((rows, m_cols), jnp.int32).at[
        jnp.arange(rows)[:, None, None],
        jnp.broadcast_to(pred_cols[None], (rows,) + pred_cols.shape),
    ].add(live.astype(jnp.int32))
    write_out = (n_succ[None, :] - consumed > 0) | (n_succ[None, :] == 0)

    # padded predecessor positions per schedule step (sentinel T -> the
    # zero slot of the end vector, matching the oracle's max(..., 0)) —
    # the layout every timing backend consumes
    ppos = jnp.where(pred_valid[l_seq],                   # (T, W)
                     ppos_mat[b_seq, l_seq], T)

    # flat (rows*M) gather index of schedule step t into the row-major
    # cost tables — the fused megakernel's in-kernel pass-A index, and the
    # host-side tproc_sched gather index for the other backends
    sched_idx = (b_seq * m_cols + l_seq).astype(jnp.int32)  # (T,)

    return dict(chip_seq=chip_seq, elide=elide, write_out=write_out,
                nop_mask=nop_mask, hop_mask=hop_mask, dram_mask=dram_mask,
                b_seq=b_seq, l_seq=l_seq, ppos=ppos, sched_idx=sched_idx)


def _cost_pass(struct, lc, pred_cols, dram_hops, flow_of_chip, ws_resident,
               out_bytes, comp_s, comp_e, weight_b, psum_b, output_b, rr,
               stream_b, extra_w, dram_bw, nop_bw):
    """Per-op ``T_proc`` in *table* order (rows, M) + total energy for one
    (batch, individual) pair given the individual's structural pass. The
    schedule-order gather (pass A) is left to the timing stage: the dense
    and unfused-pallas backends gather on the host side of the kernel via
    ``struct["sched_idx"]``, the fused megakernel gathers in-kernel."""
    rows, m_cols = lc.shape
    ws_idx = DATAFLOWS.index("WS")

    ob_w = out_bytes[:, pred_cols]                        # (rows, M, W)
    nop_in = jnp.sum(struct["nop_mask"] * ob_w, axis=-1)
    nop_hops_in = jnp.sum(struct["hop_mask"] * ob_w, axis=-1)
    dram_in = jnp.sum(struct["dram_mask"] * ob_w, axis=-1)

    op_df = flow_of_chip[lc]                              # (rows, M)
    bi = jnp.arange(rows)[:, None]
    li = jnp.arange(m_cols)[None, :]
    g = lambda tab: tab[bi, li, op_df]
    comp = g(comp_s)
    cene = g(comp_e)
    w_b = g(weight_b)
    ps_b = g(psum_b)
    o_b = g(output_b)
    rr_g = g(rr)

    elide_ok = struct["elide"] & (op_df == ws_idx) & ws_resident
    load_w = jnp.where(elide_ok, 0.0, w_b)
    w_out = jnp.where(struct["write_out"], o_b, 0.0)
    dram_bytes = (load_w + dram_in * rr_g + stream_b
                  + w_out + ps_b + extra_w)
    t_dram = dram_bytes / dram_bw
    t_nop = nop_in / nop_bw
    t_proc = jnp.maximum(comp, jnp.maximum(t_dram, t_nop))

    e_dram = jnp.sum(dram_bytes) * E_DRAM_PJ_PER_BYTE
    e_nop = jnp.sum(nop_hops_in + dram_bytes * dram_hops[lc]) \
        * E_NOP_PJ_PER_BYTE_HOP
    energy_pj = jnp.sum(cene) + e_dram + e_nop

    return t_proc, energy_pj                              # (rows, M)


def _gather_sched(tproc_flat, sched_idx):
    """Pass A as an XLA gather: flat cost rows (B, P, L) + per-individual
    schedule index (P, T) -> scheduled ``T_proc`` (B, P, T). Bitwise the
    old ``t_proc[b_seq, l_seq]`` gather (same elements, same dtype)."""
    nb, pop, _ = tproc_flat.shape
    idx = jnp.broadcast_to(sched_idx[None],
                           (nb, pop, sched_idx.shape[-1]))
    return jnp.take_along_axis(tproc_flat, idx, axis=-1)


def _pass_ab(tproc_flat, sched_idx, chip_seq, ppos, n_chips: int,
             backend: str, interpret: bool, grid_order: str):
    """Backend-dispatched pass A (gather) + pass B (timing recurrence):
    tproc_flat (B, P, L=rows*M), sched_idx (P, T), chip_seq (P, T),
    ppos (P, T, W) -> (end (B, P, T), chip_free (B, P, C)).

    ``fused`` hands the un-gathered rows straight to the megakernel (the
    (B, P, T) tproc_sched never exists outside SMEM); every other backend
    gathers here and the stages fuse — or not — at XLA's discretion.
    ``fused_host`` is the off-TPU route of the fused backend: one fused
    XLA program, bitwise-identical to ``dense`` by construction (float max
    is exact, one add per step in identical order). Its device operations
    sit under the ``timing_pass`` named scope (after ``structural_pass``
    and ``cost_pass``), whichever backend runs."""
    with jax.named_scope("timing_pass"):
        if backend == "fused":
            from ..kernels.mapping_eval import mapping_eval_fused

            return mapping_eval_fused(tproc_flat, sched_idx, chip_seq, ppos,
                                      n_chips, grid_order=grid_order,
                                      interpret=interpret)
        tproc = _gather_sched(tproc_flat, sched_idx)
        if backend == "pallas":
            from ..kernels.mapping_eval import mapping_eval

            return mapping_eval(tproc, chip_seq, ppos, n_chips,
                                interpret=interpret)
        # dense and fused_host: the proven batched-scan formulation
        per_p = jax.vmap(lambda tp, c, pp: dense_pass_b(tp, c, pp, n_chips))
        return jax.vmap(lambda tp: per_p(tp, chip_seq, ppos))(tproc)


def _population_pass_impl(
    order_rc,      # (P, T, 2) int32 scheduled (row, col) order
    l2c,           # (P, rows, M) int32
    n_succ,        # (M,) int32
    pred_cols,     # (M, W) int32 padded predecessor columns
    pred_valid,    # (M, W) bool
    hops,          # (C, C) float32
    dram_hops,     # (C,) float32
    flow_of_chip,  # (C,) int32
    ws_resident,   # (rows, M) bool
    out_bytes,     # (rows, M) float32
    comp_s,        # (rows, M, D)
    comp_e,        # (rows, M, D)
    weight_b,      # (rows, M, D)
    psum_b,        # (rows, M, D)
    output_b,      # (rows, M, D)
    rr,            # (rows, M, D)
    stream_b,      # (rows, M)
    extra_w,       # (rows, M)
    dram_bw,       # ()
    nop_bw,        # ()
    n_chips: int,
    backend: str = "dense",
    interpret: bool = False,
    full: bool = False,
    grid_order: str = "batch_major",
):
    with jax.named_scope("structural_pass"):
        struct = jax.vmap(
            lambda o, lc: _structural_pass(o, lc, n_succ, hops, pred_cols,
                                           pred_valid, n_chips)
        )(order_rc, l2c)
    with jax.named_scope("cost_pass"):
        tproc, energy = jax.vmap(
            lambda s, lc: _cost_pass(s, lc, pred_cols, dram_hops, flow_of_chip,
                                     ws_resident, out_bytes, comp_s, comp_e,
                                     weight_b, psum_b, output_b, rr, stream_b,
                                     extra_w, dram_bw, nop_bw)
        )(struct, l2c)                                # (P, rows, M), (P,)
    tproc_flat = tproc.reshape(tproc.shape[0], -1)[None]  # (1, P, L)
    end, free = _pass_ab(tproc_flat, struct["sched_idx"],
                         struct["chip_seq"], struct["ppos"],
                         n_chips, backend, interpret, grid_order)
    lat = jnp.max(end[0], axis=-1)
    if full:        # the O(P*T) matrices leave the device only on request
        tproc_sched = _gather_sched(tproc_flat, struct["sched_idx"])[0]
        return lat, energy, end[0], free[0], tproc_sched
    return lat, energy


_population_pass = partial(
    jax.jit, static_argnames=("n_chips", "backend", "interpret", "full",
                              "grid_order"))(
    _population_pass_impl)


def _grouped_population_pass_impl(
    order_rc,      # (P, T, 2) — shared by every batch of the group
    l2c,           # (P, rows, M)
    n_succ, pred_cols, pred_valid, hops, dram_hops, flow_of_chip,
    ws_resident,   # (B, rows, M)
    out_bytes,     # (B, rows, M)
    comp_s, comp_e, weight_b, psum_b, output_b, rr,   # (B, rows, M, D)
    stream_b, extra_w,                                # (B, rows, M)
    dram_bw, nop_bw,
    n_chips: int,
    backend: str = "dense",
    interpret: bool = False,
    full: bool = False,
    grid_order: str = "batch_major",
):
    # structural pass once per individual — shared across the group's
    # batches (it depends on the mapping only, not the byte tables)
    with jax.named_scope("structural_pass"):
        struct = jax.vmap(
            lambda o, lc: _structural_pass(o, lc, n_succ, hops, pred_cols,
                                           pred_valid, n_chips)
        )(order_rc, l2c)

    def per_batch(ws_r, ob, cs, ce, wb, pb, o_b, rr_b, sb, ew):
        return jax.vmap(
            lambda s, lc: _cost_pass(s, lc, pred_cols, dram_hops,
                                     flow_of_chip, ws_r, ob, cs, ce, wb,
                                     pb, o_b, rr_b, sb, ew, dram_bw, nop_bw)
        )(struct, l2c)

    with jax.named_scope("cost_pass"):
        tproc, energy = jax.vmap(per_batch)(
            ws_resident, out_bytes, comp_s, comp_e, weight_b, psum_b, output_b,
            rr, stream_b, extra_w)                    # (B, P, rows, M), (B, P)
    tproc_flat = tproc.reshape(tproc.shape[:2] + (-1,))   # (B, P, L)
    end, free = _pass_ab(tproc_flat, struct["sched_idx"],
                         struct["chip_seq"], struct["ppos"],
                         n_chips, backend, interpret, grid_order)
    lat = jnp.max(end, axis=-1)
    if full:        # the O(B*P*T) matrices leave the device only on request
        tproc_sched = _gather_sched(tproc_flat, struct["sched_idx"])
        return lat, energy, end, free, tproc_sched
    return lat, energy


_grouped_population_pass = partial(
    jax.jit, static_argnames=("n_chips", "backend", "interpret", "full",
                              "grid_order"))(
    _grouped_population_pass_impl)


# --------------------------------------------------------------------------
# Population sharding over a device mesh
#
# All per-individual work is a vmap, so sharding the population axis is
# pure data parallelism: shard_map hands each device its population slice
# and the device runs the SAME program the single-device path jits
# (including the pallas kernel when selected). Per-individual results are
# therefore bit-identical to the unsharded evaluator — the parity suite
# (tests/test_sharded_eval.py) locks this down under 8 forced host devices.
# --------------------------------------------------------------------------

_POP_AXIS = "pop"


def resolve_mesh(devices=None) -> "Mesh | None":
    """Resolve the evaluators' ``devices=`` knob into a 1-D population mesh.

    ``None`` -> all local devices (the default: a multi-device host shards
    automatically); an int N -> the first N local devices; a sequence of
    ``jax.Device`` -> exactly those (batched BO uses this to pin one
    hardware point per device); a ``Mesh`` -> itself (must be 1-D).

    Returns ``None`` for the single-*default*-device case: the evaluators
    then take the exact pre-sharding code path, so single-device behaviour
    is bit-identical to older revisions by construction. A single
    non-default device still gets a 1-device mesh (that is how work is
    pinned off device 0)."""
    if isinstance(devices, Mesh):
        if len(devices.axis_names) != 1:
            raise ValueError("population mesh must be 1-D, got axes "
                             f"{devices.axis_names!r}")
        devs = list(devices.devices.flat)
    elif devices is None:
        devs = list(jax.devices())
    elif isinstance(devices, int):
        local = jax.devices()
        if not 1 <= devices <= len(local):
            raise ValueError(f"devices={devices} but {len(local)} local "
                             "devices are available")
        devs = local[:devices]
    else:
        devs = list(devices)
        if not devs:
            raise ValueError("devices= must name at least one device")
    if len(devs) == 1 and devs[0] == jax.devices()[0]:
        return None
    return Mesh(np.array(devs), (_POP_AXIS,))


def _mesh_key(mesh: "Mesh") -> tuple:
    return tuple(d.id for d in mesh.devices.flat)


def _replicated(arrays: dict, mesh: "Mesh") -> dict:
    """Place every array fully replicated on the mesh (one resident copy
    per device) so the sharded passes never re-broadcast per call."""
    sh = NamedSharding(mesh, PartitionSpec())
    return {k: jax.device_put(v, sh) for k, v in arrays.items()}


def pad_population(orders: np.ndarray, l2c: np.ndarray,
                   multiple: int) -> tuple[np.ndarray, np.ndarray, int]:
    """Pad the population axis (axis 0 of both arrays) up to a multiple of
    the device count by repeating the last individual. Individuals are
    evaluated independently, so padding is masked out by slicing the
    outputs back to the true population size — it can never contaminate
    real results. Returns ``(orders, l2c, true_population)``.

    Pad-lane audit (locked by tests/test_sharded_eval.py): the ONLY
    consumers are the two ``_run`` methods, and both slice *every*
    output — lat/energy AND the full-matrix end/free/tproc five-tuple —
    back to ``true_population`` before anything reads them, so a padded
    lane can never win selection or leak into a timing matrix. The
    pallas/fused kernels need no extra grid padding of their own: their
    population blocks are size 1, so any population size divides the
    grid exactly."""
    p = orders.shape[0]
    pad = (-p) % multiple
    if pad:
        orders = np.concatenate(
            [orders, np.repeat(orders[-1:], pad, axis=0)])
        l2c = np.concatenate([l2c, np.repeat(l2c[-1:], pad, axis=0)])
    return orders, l2c, p


_SHARDED_PASS_CACHE: dict = {}
_SHARDED_PASS_LOCK = threading.Lock()


def _sharded_pass(mesh: "Mesh", grouped: bool, n_chips: int, backend: str,
                  interpret: bool, full: bool,
                  grid_order: str = "batch_major"):
    """``jit(shard_map(...))`` wrapper over the population axis, cached per
    (mesh devices, grouped, statics) for the process lifetime — like the
    unsharded passes, repeated searches on the same shapes never rebuild.
    The statics dict rides along replicated (in_specs ``P()``)."""
    key = (_mesh_key(mesh), grouped, n_chips, backend, interpret, full,
           grid_order)
    with _SHARDED_PASS_LOCK:
        fn = _SHARDED_PASS_CACHE.get(key)
    if fn is not None:
        return fn
    impl = _grouped_population_pass_impl if grouped else _population_pass_impl

    def body(order_rc, l2c, static):
        return impl(order_rc, l2c, n_chips=n_chips, backend=backend,
                    interpret=interpret, full=full, grid_order=grid_order,
                    **static)

    # population axis: 0 on every output of the flat pass, 1 on the
    # grouped pass's (B, P, ...) outputs
    out_spec = (PartitionSpec(None, _POP_AXIS) if grouped
                else PartitionSpec(_POP_AXIS))
    n_out = 5 if full else 2
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(_POP_AXIS), PartitionSpec(_POP_AXIS),
                  PartitionSpec()),
        out_specs=(out_spec,) * n_out,
        check_vma=False))
    with _SHARDED_PASS_LOCK:
        _SHARDED_PASS_CACHE.setdefault(key, fn)
        return _SHARDED_PASS_CACHE[key]


def jit_cache_sizes() -> dict:
    """Compile-cache sizes of the jitted entry points — one entry per
    distinct (P, T, rows, M, C[, B], backend) key across the process
    lifetime (plus one ``sharded_*`` wrapper per mesh signature). Used by
    tests/benchmarks to assert nothing retraces per generation."""
    with _SHARDED_PASS_LOCK:
        sharded_fns = list(_SHARDED_PASS_CACHE.values())
    return {
        "population_pass": int(_population_pass._cache_size()),
        "grouped_population_pass": int(_grouped_population_pass._cache_size()),
        "sharded_pass_wrappers": len(sharded_fns),
        "sharded_pass_compiles": sum(int(f._cache_size())
                                     for f in sharded_fns),
    }


def _shared_statics(graph: ExecutionGraph, hw: HardwareConfig) -> dict:
    pred_cols, pred_valid = padded_predecessor_columns(
        [m.pred_lo for m in graph.layers], [m.pred_hi for m in graph.layers])
    m_cols = graph.n_cols
    n_succ = np.zeros(m_cols, dtype=np.int32)
    for l in range(m_cols):
        n_succ[pred_cols[l][pred_valid[l]]] += 1
    C = hw.n_chiplets
    hops = np.zeros((C, C), dtype=np.float32)
    for a in range(C):
        for b in range(C):
            hops[a, b] = hw.hops(a, b)
    return dict(
        n_succ=jnp.asarray(n_succ),
        pred_cols=jnp.asarray(pred_cols),
        pred_valid=jnp.asarray(pred_valid),
        hops=jnp.asarray(hops),
        dram_hops=jnp.asarray(
            np.array([hw.dram_hops(c) for c in range(C)], np.float32)),
        flow_of_chip=jnp.asarray(
            np.array([DATAFLOWS.index(f) for f in hw.layout], np.int32)),
        dram_bw=jnp.float32(hw.dram_bw),
        nop_bw=jnp.float32(hw.nop_bw),
    )


def _table_arrays(t: CostTables) -> dict:
    return dict(
        ws_resident=t.ws_resident,
        out_bytes=t.out_act_bytes.astype(np.float32),
        comp_s=t.comp_seconds.astype(np.float32),
        comp_e=t.comp_energy_pj.astype(np.float32),
        weight_b=t.weight_bytes.astype(np.float32),
        psum_b=t.psum_bytes.astype(np.float32),
        output_b=t.output_bytes.astype(np.float32),
        rr=t.input_reread.astype(np.float32),
        stream_b=t.stream_bytes.astype(np.float32),
        extra_w=t.extra_write_bytes.astype(np.float32),
    )


# --------------------------------------------------------------------------
# Persistent device-resident table buffers
#
# The stacked (B, rows, M, D) table tensors are the heaviest host->device
# upload of a search; they depend only on the CostTables identity and the
# device placement, so one keyed cache pins them on device across GA
# generations, across search_mapping calls on the same scenario, and
# across evaluator instances. Keys are object ids plus a device signature
# (the mesh's device ids, or None for the single-default-device path):
# a sharded evaluator gets its buffers replicated once per mesh device and
# never collides with the single-device entry for the same tables. The
# cache holds the tables themselves so a live entry's ids can never be
# recycled. Eviction is LRU (hits refresh recency) — FIFO would evict the
# scenario's own hot buffers mid-sweep. Lock-guarded: batched BO prices
# several hardware points from worker threads.
# --------------------------------------------------------------------------

_DEVICE_TABLE_CACHE: "OrderedDict" = OrderedDict()
_DEVICE_CACHE_CAPACITY = 64
_DEVICE_CACHE_STATS = {"hits": 0, "misses": 0}
_DEVICE_CACHE_LOCK = threading.Lock()


def _stacked_device_tables(tables: "tuple[CostTables, ...]",
                           mesh: "Mesh | None" = None) -> dict:
    # identity keys are safe HERE: the cache value stores the `tables`
    # tuple itself, so every keyed object stays alive (its id cannot
    # recycle) for exactly as long as its cache entry exists
    key = (None if mesh is None else _mesh_key(mesh),
           tuple(id(t) for t in tables))  # repro-lint: disable=RL005
    with _DEVICE_CACHE_LOCK:
        hit = _DEVICE_TABLE_CACHE.get(key)
        if hit is not None:
            _DEVICE_CACHE_STATS["hits"] += 1
            _DEVICE_TABLE_CACHE.move_to_end(key)
            return hit[1]
        _DEVICE_CACHE_STATS["misses"] += 1
        if len(_DEVICE_TABLE_CACHE) >= _DEVICE_CACHE_CAPACITY:
            _DEVICE_TABLE_CACHE.popitem(last=False)               # LRU
        per_batch = [_table_arrays(t) for t in tables]
        if len(tables) == 1:
            host = per_batch[0]
        else:
            host = {k: np.stack([arrs[k] for arrs in per_batch])
                    for k in per_batch[0]}
        if mesh is None:
            stacked = {k: jnp.asarray(v) for k, v in host.items()}
        else:
            stacked = _replicated(host, mesh)
        _DEVICE_TABLE_CACHE[key] = (tables, stacked)
        return stacked


def device_table_cache_stats() -> dict:
    with _DEVICE_CACHE_LOCK:
        return dict(_DEVICE_CACHE_STATS, entries=len(_DEVICE_TABLE_CACHE))


def device_table_resident_bytes() -> "dict[str, int]":
    """Per-device resident bytes of the cached stacked table buffers —
    replication cost is visible device by device in ``cache_stats()``."""
    with _DEVICE_CACHE_LOCK:
        entries = [stacked for (_t, stacked) in _DEVICE_TABLE_CACHE.values()]
    out: "dict[str, int]" = {}
    for stacked in entries:
        for arr in stacked.values():
            for shard in getattr(arr, "addressable_shards", []):
                dev = str(shard.device)
                out[dev] = out.get(dev, 0) + int(shard.data.nbytes)
    return out


def _resolve_jax_backend(backend) -> tuple[str, bool, str]:
    """(name, interpret, grid_order) statics for the jitted passes; the
    oracle backend has no jitted path — compass routes it to the numpy
    evaluator. The fused backend resolves to ``"fused"`` (megakernel) when
    interpreting or on a TPU, else to ``"fused_host"`` — the fused XLA
    program, counted as a ``fused->host`` reroute (never silently
    ``dense``: dispatch stats always name the path that actually ran)."""
    be = resolve_timing_backend(backend)
    if isinstance(be, OracleTimingBackend):
        raise ValueError(
            "the 'oracle' timing backend is the pure-numpy reference path; "
            "use evaluator.evaluate / compass(use_jax=False) instead of the "
            "population evaluators")
    if isinstance(be, FusedTimingBackend):
        interpret = bool(be._interpret())
        grid_order = be.grid_order or default_grid_order()
        if interpret or jax.default_backend() == "tpu":
            return "fused", interpret, grid_order
        record_backend_fallback("fused->host")
        return "fused_host", False, grid_order
    if isinstance(be, PallasTimingBackend):
        return "pallas", bool(be._interpret()), "batch_major"
    return "dense", False, "batch_major"


@dataclass
class PopulationEvaluator:
    """Evaluates GA populations on-device; matches the numpy oracle.

    ``devices`` shards the population axis over a device mesh (see
    :func:`resolve_mesh`); the default ``None`` uses all local devices and
    collapses to the exact single-device path on a one-device host."""

    graph: ExecutionGraph
    tables: CostTables
    hw: HardwareConfig
    backend: "TimingBackend | str | None" = None
    devices: "int | Sequence | Mesh | None" = None

    def __post_init__(self):
        g, hw = self.graph, self.hw
        self._backend, self._interpret, self._grid_order = \
            _resolve_jax_backend(self.backend)
        self._mesh = resolve_mesh(self.devices)
        statics = _shared_statics(g, hw)
        if self._mesh is not None:
            statics = _replicated(statics, self._mesh)
        self._static = dict(
            statics,
            **_stacked_device_tables((self.tables,), mesh=self._mesh),
        )
        self._n_chips = hw.n_chiplets
        self._order_cache = ScheduledOrderCache(g.rows, g.n_cols)

    def _run(self, population, full: bool = False):
        record_backend_dispatch(self._backend)
        pop = as_stacked(population)
        # function-level import: repro.analysis depends on core submodules
        from ..analysis.mapping import assert_population_legal, \
            verify_env_enabled
        if verify_env_enabled():
            # host-side legality gate (REPRO_VERIFY_MAPPINGS=1): raise on
            # illegal encodings instead of letting the jitted gathers
            # clamp/wrap them into silently-wrong prices
            assert_population_legal(pop, self._n_chips, graph=self.graph)
        orders = self._order_cache.orders(pop.segmentation)
        with telemetry.span("repro.eval.dispatch"):
            if self._mesh is None:
                return _population_pass(
                    jnp.asarray(orders), jnp.asarray(pop.layer_to_chip),
                    n_chips=self._n_chips, backend=self._backend,
                    interpret=self._interpret, full=full,
                    grid_order=self._grid_order, **self._static)
            orders, l2c, p0 = pad_population(
                np.asarray(orders), np.asarray(pop.layer_to_chip),
                self._mesh.size)
            fn = _sharded_pass(self._mesh, False, self._n_chips, self._backend,
                               self._interpret, full, self._grid_order)
            out = fn(orders, l2c, self._static)
            if p0 != orders.shape[0]:
                out = tuple(o[:p0] for o in out)
            return out

    def evaluate_population(
        self, population: "Sequence[MappingEncoding]"
    ) -> tuple[np.ndarray, np.ndarray]:
        """Returns (latency_s, energy_j) arrays over the population.
        Accepts a list of encodings or a ``StackedPopulation``."""
        with telemetry.span("repro.eval"):
            lat, en_pj = self._run(population)
            with telemetry.span("repro.eval.fetch"):
                lat = np.asarray(lat, np.float64)
                en_pj = np.asarray(en_pj, np.float64)
            scale = self.graph.scale
            return lat * scale, en_pj * 1e-12 * scale

    def timing_matrix(self, population) -> TimingMatrix:
        """Full per-op timing matrix (P, T)/(P, C), block scale applied."""
        _, _, end, free, tproc = self._run(population, full=True)
        scale = self.graph.scale
        end = np.asarray(end, np.float64) * scale
        return TimingMatrix(
            op_start_s=end - np.asarray(tproc, np.float64) * scale,
            op_end_s=end,
            chip_free_s=np.asarray(free, np.float64) * scale)


@dataclass
class GroupPopulationEvaluator:
    """Evaluates a GA population against ALL structurally-identical batches
    of a ``search_mapping`` group in one jitted call per generation: the
    per-batch cost tables live on device in a persistent keyed cache and
    are vmapped over, while the mapping-structural pass runs once per
    individual. Returns (B, P) latency/energy; ``timing_matrix`` exposes
    the full per-op (B, P, T) matrix the SLO objectives fold.

    ``devices`` shards the population axis (see :func:`resolve_mesh`):
    the batch axis stays whole on every device (tables replicated), the
    population splits — the axis GA scaling actually grows."""

    graphs: Sequence[ExecutionGraph]
    tables: Sequence[CostTables]
    hw: HardwareConfig
    backend: "TimingBackend | str | None" = None
    devices: "int | Sequence | Mesh | None" = None

    def __post_init__(self):
        g0 = self.graphs[0]
        assert all(g.rows == g0.rows and g.n_cols == g0.n_cols
                   for g in self.graphs), "group batches must share (rows, M)"
        # the structural pass is shared, so the dependency structure must be
        # identical too — equal shape alone does not guarantee it
        preds0 = [(m.pred_lo, m.pred_hi) for m in g0.layers]
        assert all([(m.pred_lo, m.pred_hi) for m in g.layers] == preds0
                   for g in self.graphs), \
            "group batches must share predecessor intervals"
        self._backend, self._interpret, self._grid_order = \
            _resolve_jax_backend(self.backend)
        self._mesh = resolve_mesh(self.devices)
        stacked = _stacked_device_tables(tuple(self.tables), mesh=self._mesh)
        if len(self.tables) == 1:
            stacked = {k: v[None] for k, v in stacked.items()}
        statics = _shared_statics(g0, self.hw)
        if self._mesh is not None:
            statics = _replicated(statics, self._mesh)
        self._static = dict(statics, **stacked)
        self._n_chips = self.hw.n_chiplets
        self._order_cache = ScheduledOrderCache(g0.rows, g0.n_cols)
        self._scales = np.array([g.scale for g in self.graphs])

    @property
    def n_batches(self) -> int:
        return len(self.graphs)

    def _run(self, population, full: bool = False):
        record_backend_dispatch(self._backend)
        pop = as_stacked(population)
        from ..analysis.mapping import assert_population_legal, \
            verify_env_enabled
        if verify_env_enabled():
            # host-side legality gate — every batch of the group shares
            # one dependency structure (asserted in __post_init__), so
            # checking against graphs[0] covers them all
            assert_population_legal(pop, self._n_chips,
                                    graph=self.graphs[0])
        orders = self._order_cache.orders(pop.segmentation)
        with telemetry.span("repro.eval.dispatch"):
            if self._mesh is None:
                return _grouped_population_pass(
                    jnp.asarray(orders), jnp.asarray(pop.layer_to_chip),
                    n_chips=self._n_chips, backend=self._backend,
                    interpret=self._interpret, full=full,
                    grid_order=self._grid_order, **self._static)
            orders, l2c, p0 = pad_population(
                np.asarray(orders), np.asarray(pop.layer_to_chip),
                self._mesh.size)
            fn = _sharded_pass(self._mesh, True, self._n_chips, self._backend,
                               self._interpret, full, self._grid_order)
            out = fn(orders, l2c, self._static)
            if p0 != orders.shape[0]:
                out = tuple(o[:, :p0] for o in out)
            return out

    def evaluate_population(
        self, population
    ) -> tuple[np.ndarray, np.ndarray]:
        """population (list of encodings or StackedPopulation) ->
        ((B, P) latency_s, (B, P) energy_j)."""
        with telemetry.span("repro.eval"):
            lat, en_pj = self._run(population)
            with telemetry.span("repro.eval.fetch"):
                lat = np.asarray(lat, np.float64)
                en_pj = np.asarray(en_pj, np.float64)
            scale = self._scales[:, None]
            return lat * scale, en_pj * 1e-12 * scale

    def timing_matrix(self, population) -> TimingMatrix:
        """Full (B, P, T) timing matrix, block scale applied. The GA hot
        loop (``evaluate_population``) never materialises these outputs —
        only this entry point compiles the ``full`` variant."""
        _, _, end, free, tproc = self._run(population, full=True)
        scale = self._scales[:, None, None]
        end = np.asarray(end, np.float64) * scale
        return TimingMatrix(
            op_start_s=end - np.asarray(tproc, np.float64) * scale,
            op_end_s=end,
            chip_free_s=np.asarray(free, np.float64) * scale)


@dataclass
class JointStreamEvaluator:
    """Whole-scenario SLO fitness for joint-mode cross-group co-search.

    A joint GA individual carries one encoding per structure group; this
    evaluator runs every group's population evaluator (one jitted call per
    group per generation), assembles the scenario's full (P, n_batches)
    per-iteration latency matrix — NO best-known splicing: every batch's
    latency comes from the same joint candidate — and folds it into
    per-request timings in one jitted ``timing.fold_request_timings``
    call, scored by the SLO objective.

    Each ``scores`` call also refreshes the per-group *violation
    attribution* of the generation's best candidate
    (``timing.attribute_group_violations`` over the objective's
    ``violations`` mask): :meth:`group_bias` exposes it so
    ``ga.joint_ga_search`` can bias its per-group mutation mask toward
    the group whose spliced latencies dominate the current SLO
    violations.

    ``group_evals`` maps group key -> ``eval(pop) -> ((B, P) latency_s,
    (B, P) energy_j)`` — a ``GroupPopulationEvaluator.evaluate_population``
    or the numpy-oracle host path, so joint mode works on every timing
    backend; ``groups`` maps group key -> rollout batch indices. Device
    sharding is inherited transitively: when the group evaluators carry a
    ``devices=`` mesh, every group's population shards over it and the
    assembled latency matrix (host-side) is already in population order —
    joint scores are bit-identical across device counts."""

    group_evals: "dict[tuple, object]"
    groups: "dict[tuple, list[int]]"
    rollout: object
    objective: object
    # set False when the consumer will never read group_bias (e.g.
    # CoSearchConfig(violation_bias=0)): skips the per-generation
    # violation-mask + attribution work entirely
    track_bias: bool = True

    def __post_init__(self):
        self._last_bias: "np.ndarray | None" = None

    @property
    def n_batches(self) -> int:
        return sum(len(v) for v in self.groups.values())

    def latency_matrix(self, pops: "dict[tuple, object]") -> np.ndarray:
        """(P, n_batches) per-iteration latencies of the joint population
        (``pops``: group key -> index-aligned ``StackedPopulation``)."""
        full = None
        for key, idxs in self.groups.items():
            lat, _ = self.group_evals[key](pops[key])    # (B, P)
            lat = np.asarray(lat, dtype=float)
            if full is None:
                full = np.empty((lat.shape[1], self.n_batches))
            full[:, idxs] = lat.T
        return full

    def scores(self, pops: "dict[tuple, object]") -> np.ndarray:
        """(P,) minimised SLO scores of the joint population."""
        from .streams import RequestTimings

        full = self.latency_matrix(pops)
        timings = fold_request_timings(self.rollout, full)
        s = np.asarray(self.objective.score_timings(timings), dtype=float)
        violations = getattr(self.objective, "violations", None)
        if self.track_bias and violations is not None and s.size:
            # attribution only needs the best candidate: slice its row out
            # BEFORE computing the violation mask, so percentile/SLO work
            # is 1/P of the population-wide computation per generation
            best = int(np.argmin(s))
            bt = RequestTimings(
                ttft_s=timings.ttft_s[best], tpot_s=timings.tpot_s[best],
                finished=timings.finished[best], warm=timings.warm,
                makespan_s=float(np.asarray(timings.makespan_s)[best]),
                synthetic=timings.synthetic)
            viol = np.asarray(violations(bt), dtype=bool)
            self._last_bias = attribute_group_violations(
                self.rollout, full[best], viol,
                list(self.groups.values()))
        return s

    def group_bias(self) -> "np.ndarray | None":
        """Per-group violation weights of the latest generation's best
        candidate ((G,) in ``groups`` order, summing to 1), or ``None``
        before the first ``scores`` call / for non-SLO objectives."""
        return self._last_bias
