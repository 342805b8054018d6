"""Pluggable timing backends — ONE evaluation stack from numpy oracle to
Pallas kernel (paper §V-C, pass B).

The evaluation engine runs two passes over a mapping's scheduled op order:
the dense Algorithm-2 flag pass (structural, mapping-only) and the *timing
recurrence* (pass B) — the only truly sequential computation in the GA
inner loop:

    start_t = max(chip_free[chip_t], max_w end[ppos[t, w]])
    end[t] = chip_free[chip_t] = start_t + t_proc[t]

This module defines the :class:`TimingBackend` protocol for pass B with
three interchangeable implementations sharing one array contract — the
*padded predecessor-position layout*: ``t_proc`` (B, P, T) per-op
processing times in scheduled order, ``chip`` (P, T) chiplet per step, and
``ppos`` (P, T, W) positions of each step's predecessors in the same
order, padded with the sentinel T (which indexes a permanently-zero slot
of the end vector, the oracle's ``max(..., 0)``):

* ``oracle`` — pure-numpy Python loop, the reference semantics;
* ``dense``  — batched ``lax.scan``, the XLA path (default);
* ``pallas`` — ``repro.kernels.mapping_eval``, the SMEM-resident TPU
  kernel (one (batch, individual) recurrence per grid step); off-TPU it
  auto-falls back to ``dense`` unless constructed with ``interpret=True``
  (CPU CI runs the exact TPU code path interpreted);
* ``fused``  — ``repro.kernels.mapping_eval_fused``, the pass-A + pass-B
  megakernel: the tproc gather and the recurrence run in ONE SMEM-resident
  program (tunable grid order, autotuned per shape on TPU). Off-TPU it
  routes to ``mapping_eval_fused_host`` — the same fused contract as one
  jitted XLA program, bitwise-identical to ``dense`` — instead of silently
  degrading; the reroute is counted in :func:`timing_backend_stats`.

Every ``pass_b`` dispatch and every silent off-TPU reroute is counted in
:func:`timing_backend_stats` (surfaced via ``repro.core.cache_stats()``),
so benchmark records can prove which kernel actually ran.

Every backend returns the full **timing matrix** — per-op start/end times
plus per-chiplet free times — not just a makespan, so
:func:`fold_request_timings` can turn per-iteration latencies into true
per-request TTFT/TPOT/goodput *inside* the GA loop (SLO-aware fitness; see
``repro.core.objectives``).

The module also owns the persistent cost-table cache: ``CostTables`` (and
the execution graphs they are built from) are keyed on the
(workload, micro-batch, chiplet-spec) identity and reused across GA
generations, across ``search_mapping`` calls, and across BO iterations
that share a chiplet spec — the second search on a scenario never rebuilds
a table.

Backend selection: ``Scenario(timing_backend=...)`` > the
``REPRO_TIMING_BACKEND`` environment variable > ``"dense"``.
"""
from __future__ import annotations

import os
import threading
import warnings
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

__all__ = [
    "TimingBackend", "TimingMatrix",
    "OracleTimingBackend", "DenseTimingBackend", "PallasTimingBackend",
    "FusedTimingBackend",
    "TIMING_BACKENDS", "get_timing_backend", "resolve_timing_backend",
    "padded_predecessor_columns", "padded_predecessor_positions",
    "dense_pass_b", "fold_request_timings", "splice_latencies",
    "attribute_group_violations",
    "get_execution_graph", "get_cost_tables", "get_graph_and_tables",
    "cost_cache_stats", "clear_cost_caches",
    "record_backend_dispatch", "record_backend_fallback",
    "timing_backend_stats", "clear_timing_backend_stats",
]

BACKEND_ENV = "REPRO_TIMING_BACKEND"
TIMING_BACKENDS = ("oracle", "dense", "pallas", "fused")


# --------------------------------------------------------------------------
# Backend dispatch observability
#
# Which kernel actually ran is invisible in results (the backends agree
# bitwise or to float tolerance), so deployment surprises — e.g. 'pallas'
# silently degrading to 'dense' on a CPU host — would otherwise go
# unnoticed. Every pass_b dispatch and every implicit reroute bumps a
# counter here; repro.core.cache_stats() exposes them and the benchmarks
# embed them next to their wall numbers.
# --------------------------------------------------------------------------

_BACKEND_STATS_LOCK = threading.Lock()
_BACKEND_STATS: dict[str, dict[str, int]] = {"dispatches": {}, "fallbacks": {}}


def record_backend_dispatch(name: str, n: int = 1) -> None:
    """Count ``n`` pass-B dispatches attributed to backend ``name``
    (evaluators call this once per jitted generation call)."""
    with _BACKEND_STATS_LOCK:
        d = _BACKEND_STATS["dispatches"]
        d[name] = d.get(name, 0) + n


def record_backend_fallback(kind: str) -> None:
    """Count one implicit backend reroute, e.g. ``"pallas->dense"`` (the
    off-TPU degradation) or ``"fused->host"`` (the fused XLA path)."""
    with _BACKEND_STATS_LOCK:
        f = _BACKEND_STATS["fallbacks"]
        f[kind] = f.get(kind, 0) + 1


def timing_backend_stats() -> dict:
    """Snapshot of per-backend dispatch counts and implicit fallbacks."""
    with _BACKEND_STATS_LOCK:
        return {k: dict(v) for k, v in _BACKEND_STATS.items()}


def clear_timing_backend_stats() -> None:
    with _BACKEND_STATS_LOCK:
        for v in _BACKEND_STATS.values():
            v.clear()


# --------------------------------------------------------------------------
# Shared array contract
# --------------------------------------------------------------------------


@dataclass
class TimingMatrix:
    """Full pass-B output (seconds, graph units — callers apply the graph's
    block scale). Leading axes are free; the canonical grouped-evaluator
    shape is (batches, population)."""

    op_start_s: np.ndarray   # (..., T) scheduled-order op start times
    op_end_s: np.ndarray     # (..., T) scheduled-order op end times
    chip_free_s: np.ndarray  # (..., C) per-chiplet free (busy-until) times

    @property
    def makespan_s(self) -> np.ndarray:
        return self.op_end_s.max(axis=-1)


def padded_predecessor_columns(pred_lo, pred_hi):
    """Per-layer predecessor column intervals -> padded (M, W) column
    indices + validity mask (predecessors are contiguous intervals of
    width <= W, so narrow padded tensors replace dense (M, M) masks)."""
    pred_lo = np.asarray(pred_lo)
    pred_hi = np.asarray(pred_hi)
    m_cols = pred_lo.shape[0]
    widths = np.where(pred_lo >= 0, pred_hi - pred_lo, 0)
    w = max(int(widths.max(initial=0)), 1)
    pred_cols = np.zeros((m_cols, w), dtype=np.int32)
    pred_valid = np.zeros((m_cols, w), dtype=bool)
    for l in range(m_cols):
        if pred_lo[l] >= 0:
            n = int(pred_hi[l] - pred_lo[l])
            pred_cols[l, :n] = np.arange(pred_lo[l], pred_hi[l])
            pred_valid[l, :n] = True
    return pred_cols, pred_valid


def padded_predecessor_positions(order, pred_cols, pred_valid):
    """Scheduled (row, col) order (T, 2) -> (T, W) predecessor positions in
    the same order, padded with the sentinel T."""
    order = np.asarray(order)
    t_len = order.shape[0]
    b_seq, l_seq = order[:, 0], order[:, 1]
    rows = int(b_seq.max()) + 1
    m_cols = pred_cols.shape[0]
    pos = np.zeros((rows, m_cols), dtype=np.int32)
    pos[b_seq, l_seq] = np.arange(t_len, dtype=np.int32)
    ppos_mat = pos[:, pred_cols]                      # (rows, M, W)
    return np.where(pred_valid[l_seq], ppos_mat[b_seq, l_seq],
                    t_len).astype(np.int32)


def _as_bpt(t_proc, chip, ppos):
    """Normalise to the (B, P, T) / (P, T) / (P, T, W) contract."""
    t_proc = np.asarray(t_proc, dtype=np.float64)
    chip = np.asarray(chip)
    ppos = np.asarray(ppos)
    squeeze = t_proc.ndim == 2
    if squeeze:
        t_proc = t_proc[None]
    if chip.ndim == 1:
        chip = chip[None]
        ppos = ppos[None]
    return t_proc, chip, ppos, squeeze


# --------------------------------------------------------------------------
# Backends
# --------------------------------------------------------------------------


class TimingBackend:
    """Pass-B engine. ``pass_b`` consumes the shared scheduled-order layout
    and returns (end (B, P, T), chip_free (B, P, C)); ``timing_matrix``
    wraps the result (starts derived as end - t_proc)."""

    name = "base"

    def pass_b(self, t_proc, chip, ppos, n_chips: int):
        raise NotImplementedError

    def timing_matrix(self, t_proc, chip, ppos, n_chips: int) -> TimingMatrix:
        t_bpt, chip, ppos, squeeze = _as_bpt(t_proc, chip, ppos)
        end, free = self.pass_b(t_bpt, chip, ppos, n_chips)
        end = np.asarray(end, dtype=np.float64)
        free = np.asarray(free, dtype=np.float64)
        if squeeze:
            end, free = end[0], free[0]
        return TimingMatrix(op_start_s=end - np.asarray(t_proc),
                            op_end_s=end, chip_free_s=free)

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.name!r})"


class OracleTimingBackend(TimingBackend):
    """Pure-numpy sequential recurrence — the reference semantics every
    other backend is tested against (the explicit host path)."""

    name = "oracle"

    def pass_b(self, t_proc, chip, ppos, n_chips: int):
        record_backend_dispatch(self.name)
        t_proc, chip, ppos, _ = _as_bpt(t_proc, chip, ppos)
        n_batch, pop, t_len = t_proc.shape
        end = np.zeros((n_batch, pop, t_len))
        free = np.zeros((n_batch, pop, n_chips))
        for bi in range(n_batch):
            for pi in range(pop):
                endv = np.zeros(t_len + 1)   # slot T: sentinel, stays 0
                chip_free = np.zeros(n_chips)
                for t in range(t_len):
                    c = chip[pi, t]
                    start = max(chip_free[c], endv[ppos[pi, t]].max())
                    fin = start + t_proc[bi, pi, t]
                    endv[t] = fin
                    chip_free[c] = fin
                end[bi, pi] = endv[:t_len]
                free[bi, pi] = chip_free
        return end, free


def dense_pass_b(t_proc, chip, ppos, n_chips: int):
    """One (T,)-sequence recurrence as a ``lax.scan`` — jit/vmap-safe; the
    building block of the ``dense`` backend and of the XLA population
    evaluator. Returns (end (T,), chip_free (C,))."""
    import jax
    import jax.numpy as jnp

    t_len = t_proc.shape[0]

    def step(carry, xs):
        chip_free, end_sched = carry
        t, c, pp, tp = xs
        start = jnp.maximum(chip_free[c], jnp.max(end_sched[pp]))
        fin = start + tp
        return (chip_free.at[c].set(fin), end_sched.at[t].set(fin)), None

    (chip_free, end_sched), _ = jax.lax.scan(
        step,
        (jnp.zeros((n_chips,), t_proc.dtype),
         jnp.zeros((t_len + 1,), t_proc.dtype)),
        (jnp.arange(t_len, dtype=jnp.int32), chip, ppos, t_proc),
        unroll=min(8, t_len))
    return end_sched[:t_len], chip_free


_DENSE_CACHE: dict[str, object] = {}


def _dense_batched_fn():
    """Module-level jitted (B, P)-batched dense pass B — one compile per
    shape across the process, not per backend call."""
    import jax

    if "fn" not in _DENSE_CACHE:
        from functools import partial

        @partial(jax.jit, static_argnames=("n_chips",))
        def fn(t_proc, chip, ppos, n_chips):
            per_p = jax.vmap(dense_pass_b, in_axes=(0, 0, 0, None))
            return jax.vmap(per_p, in_axes=(0, None, None, None))(
                t_proc, chip, ppos, n_chips)

        _DENSE_CACHE["fn"] = fn
    return _DENSE_CACHE["fn"]


class DenseTimingBackend(TimingBackend):
    """Batched ``lax.scan`` over (B, P) — the default XLA path."""

    name = "dense"

    def pass_b(self, t_proc, chip, ppos, n_chips: int):
        import jax.numpy as jnp

        record_backend_dispatch(self.name)
        t_proc, chip, ppos, _ = _as_bpt(t_proc, chip, ppos)
        end, free = _dense_batched_fn()(
            jnp.asarray(t_proc, jnp.float32), jnp.asarray(chip),
            jnp.asarray(ppos), n_chips)
        return np.asarray(end), np.asarray(free)


class PallasTimingBackend(TimingBackend):
    """The ``repro.kernels.mapping_eval`` SMEM-resident recurrence.
    ``interpret=True`` runs the exact TPU code path interpreted on CPU
    (used by CI); ``interpret=None`` auto-detects (compiled on TPU,
    interpreted elsewhere) — :func:`resolve_timing_backend` instead falls
    back to ``dense`` off-TPU when interpretation was not asked for."""

    name = "pallas"

    def __init__(self, interpret: bool | None = None):
        self.interpret = interpret

    def _interpret(self) -> bool:
        if self.interpret is None:
            import jax
            return jax.default_backend() != "tpu"
        return self.interpret

    def pass_b(self, t_proc, chip, ppos, n_chips: int):
        import jax.numpy as jnp

        from ..kernels.mapping_eval import mapping_eval

        record_backend_dispatch(self.name)
        t_proc, chip, ppos, _ = _as_bpt(t_proc, chip, ppos)
        end, free = mapping_eval(
            jnp.asarray(t_proc, jnp.float32), jnp.asarray(chip),
            jnp.asarray(ppos), n_chips, interpret=self._interpret())
        return np.asarray(end), np.asarray(free)


class FusedTimingBackend(PallasTimingBackend):
    """The pass-A + pass-B megakernel
    (``repro.kernels.mapping_eval_fused``): the tproc gather and the
    timing recurrence run in one SMEM-resident program on the
    (population, batches) grid, grid order tunable/autotuned.

    Off-TPU (and not interpreting) it does NOT degrade to ``dense``: it
    runs ``mapping_eval_fused_host`` — the same fused contract as a single
    jitted XLA program, bitwise-identical to the dense scan — and counts
    the reroute as ``"fused->host"`` in :func:`timing_backend_stats`.

    The protocol-level ``pass_b`` receives already-gathered ``t_proc``;
    the kernel consumes it through an identity ``sched_idx``. The
    population evaluators instead hand the kernel the un-gathered cost
    rows (the (B, P, T) ``tproc_sched`` is never materialised there)."""

    name = "fused"

    def __init__(self, interpret: bool | None = None,
                 grid_order: str | None = None):
        super().__init__(interpret)
        self.grid_order = grid_order

    def pass_b(self, t_proc, chip, ppos, n_chips: int):
        import jax
        import jax.numpy as jnp

        from ..kernels.mapping_eval import (mapping_eval_fused,
                                            mapping_eval_fused_host)

        record_backend_dispatch(self.name)
        t_proc, chip, ppos, _ = _as_bpt(t_proc, chip, ppos)
        t_len = t_proc.shape[-1]
        sched = jnp.broadcast_to(jnp.arange(t_len, dtype=jnp.int32),
                                 chip.shape)
        interpret = self._interpret()
        if not interpret and jax.default_backend() != "tpu":
            record_backend_fallback("fused->host")
            end, free = mapping_eval_fused_host(
                jnp.asarray(t_proc, jnp.float32), sched,
                jnp.asarray(chip), jnp.asarray(ppos), n_chips)
        else:
            end, free = mapping_eval_fused(
                jnp.asarray(t_proc, jnp.float32), sched,
                jnp.asarray(chip), jnp.asarray(ppos), n_chips,
                grid_order=self.grid_order, interpret=interpret)
        return np.asarray(end), np.asarray(free)


def get_timing_backend(spec: "TimingBackend | str | None" = None
                       ) -> TimingBackend:
    """Resolve a backend name or instance; ``None`` reads the
    ``REPRO_TIMING_BACKEND`` environment variable (default ``dense``).
    No fallback logic — see :func:`resolve_timing_backend`."""
    if isinstance(spec, TimingBackend):
        return spec
    if spec is None:
        spec = os.environ.get(BACKEND_ENV, "dense")
    if spec == "oracle":
        return OracleTimingBackend()
    if spec == "dense":
        return DenseTimingBackend()
    if spec == "pallas":
        return PallasTimingBackend(interpret=False)
    if spec == "fused":
        return FusedTimingBackend(interpret=False)
    raise ValueError(f"unknown timing backend {spec!r}; choose from "
                     f"{TIMING_BACKENDS} or pass a TimingBackend instance")


def resolve_timing_backend(spec: "TimingBackend | str | None" = None,
                           ) -> TimingBackend:
    """:func:`get_timing_backend` plus the deployment rule: ``pallas``
    off-TPU degrades to ``dense`` (with a warning, counted in
    :func:`timing_backend_stats`) unless the instance explicitly asked
    for interpret mode. ``fused`` never degrades — it carries its own
    off-TPU XLA path (:func:`~repro.kernels.mapping_eval_fused_host`)."""
    be = get_timing_backend(spec)
    if (isinstance(be, PallasTimingBackend)
            and not isinstance(be, FusedTimingBackend)
            and not be.interpret):
        import jax

        if jax.default_backend() != "tpu":
            warnings.warn(
                "timing backend 'pallas' requires a TPU (or "
                "PallasTimingBackend(interpret=True) for the interpreted "
                "CPU path); falling back to 'dense'",
                RuntimeWarning, stacklevel=2)
            record_backend_fallback("pallas->dense")
            return DenseTimingBackend()
    return be


# --------------------------------------------------------------------------
# On-device per-request timing fold (rollout pricing inside the GA loop)
# --------------------------------------------------------------------------


def splice_latencies(base_lat, idxs, cand_lat) -> np.ndarray:
    """Splice one structure group's candidate latencies into the rollout's
    best-known per-batch latency vector: ``base_lat`` (N,) best-known
    latencies, ``cand_lat`` (P, k) candidate latencies for the batches at
    positions ``idxs`` -> (P, N) full latency matrices, one per candidate.
    This is the coordinate-descent coupling of the cross-group co-search
    (compass fixed-point loop); joint mode assembles the matrix from every
    group's own candidates instead and never calls this."""
    cand = np.asarray(cand_lat, dtype=float)
    full = np.repeat(np.asarray(base_lat, dtype=float)[None, :],
                     cand.shape[0], axis=0)
    full[:, idxs] = cand
    return full


_FOLD_CACHE: dict[str, object] = {}   # single "fn" slot, like _DENSE_CACHE


def _fold_fn():
    import jax
    import jax.numpy as jnp

    if "fn" not in _FOLD_CACHE:
        @jax.jit
        def fold(lat, arr_idx, fb_safe, served, db_safe, fin, steps,
                 one_tok):
            zero = jnp.zeros(lat.shape[:-1] + (1,), lat.dtype)
            cum = jnp.concatenate([zero, jnp.cumsum(lat, axis=-1)], axis=-1)
            ttft = jnp.where(served, cum[..., fb_safe + 1] - cum[..., arr_idx],
                             jnp.inf)
            tpot = jnp.where(fin,
                             (cum[..., db_safe + 1] - cum[..., fb_safe + 1])
                             / steps, jnp.inf)
            tpot = jnp.where(one_tok, 0.0, tpot)
            return ttft, tpot, cum[..., -1]

        _FOLD_CACHE["fn"] = fold
    return _FOLD_CACHE["fn"]


def fold_request_timings(rollout, batch_latency_s):
    """Price a rollout on-device: ``batch_latency_s`` (..., B) per-iteration
    latencies (any leading axes — e.g. a whole GA population) ->
    :class:`~repro.core.streams.RequestTimings` with matching leading axes.
    Semantically identical to ``StreamRollout.timings`` (tested), but the
    cumsum/gather fold is one jitted call, so SLO-aware GA fitness never
    leaves the device for the heavy part."""
    from .streams import RequestTimings

    lat = np.asarray(batch_latency_s, dtype=np.float32)
    nb = len(rollout.batches)
    assert lat.shape[-1] == nb, \
        f"expected (..., {nb}) latencies, got {lat.shape}"
    served = rollout.first_b >= 0
    fin = rollout.done_b >= 0
    fb_safe = np.where(served, rollout.first_b, 0)
    db_safe = np.where(fin, rollout.done_b, 0)
    arr_idx = np.minimum(rollout.arrival_b, nb - 1)
    steps = np.maximum(rollout.n_new_tokens - 1, 1).astype(np.float32)
    one_tok = fin & (rollout.n_new_tokens <= 1)
    ttft, tpot, makespan = _fold_fn()(
        lat, arr_idx, fb_safe, served, db_safe, fin, steps, one_tok)
    return RequestTimings(
        ttft_s=np.asarray(ttft), tpot_s=np.asarray(tpot),
        finished=np.broadcast_to(fin, np.shape(ttft)).copy(),
        warm=rollout.warm,
        makespan_s=(float(makespan) if np.ndim(makespan) == 0
                    else np.asarray(makespan)),
        synthetic=rollout.synthetic)


def attribute_group_violations(rollout, batch_latency_s, violating,
                               group_idxs) -> np.ndarray:
    """Per-group violation attribution from the timing matrix: how much of
    the SLO-violating requests' latency is owed to each structure group.

    For every violating request, its *latency window* runs from the first
    executed iteration at/after arrival to its completion iteration (or
    the end of the horizon when unfinished); each batch inside the window
    contributes its own latency. Summing those contributions per batch and
    then per owning structure group yields the group weights the joint
    co-search uses to bias its per-group mutation mask toward the group
    whose spliced latencies dominate the current violations.

    ``batch_latency_s`` (B,): the reference candidate's per-iteration
    latencies; ``violating`` (R,) bool (an objective's ``violations``
    mask); ``group_idxs``: ordered list of per-group batch-index lists.
    Returns (G,) non-negative weights summing to 1 — uniform when nothing
    violates (no signal: keep exploring every group)."""
    lat = np.asarray(batch_latency_s, dtype=float)
    assert lat.ndim == 1, "attribution needs ONE candidate's latencies"
    nb = lat.shape[0]
    viol = np.asarray(violating, dtype=bool)
    n_groups = len(group_idxs)
    uniform = np.full(n_groups, 1.0 / max(n_groups, 1))
    if n_groups == 0 or not viol.any():
        return uniform
    start = np.minimum(np.asarray(rollout.arrival_b), nb - 1)[viol]
    done = np.asarray(rollout.done_b)[viol]
    end = np.where(done >= 0, done, nb - 1)
    # interval-cover counting: +1 at start, -1 past end, prefix-sum ->
    # how many violating windows cover each batch
    delta = np.zeros(nb + 1, dtype=float)
    np.add.at(delta, start, 1.0)
    np.add.at(delta, end + 1, -1.0)
    cover = np.cumsum(delta[:-1])
    per_batch = cover * lat
    weights = np.array([per_batch[list(idxs)].sum() for idxs in group_idxs])
    total = weights.sum()
    if not np.isfinite(total) or total <= 0.0:
        return uniform
    return weights / total


# --------------------------------------------------------------------------
# Persistent cost-table / execution-graph cache
# --------------------------------------------------------------------------
#
# CostTables depend only on the (execution graph, chiplet spec) pair —
# layout/bandwidth enter at evaluation time — so one table serves every GA
# generation, every search_mapping call on the scenario, and every BO point
# sharing a chiplet spec. The device-resident stacked copies are cached one
# level up, in jax_evaluator, keyed on the host tables cached here.
#
# Eviction is LRU (hits refresh recency): under FIFO, a hardware sweep
# over more than _CACHE_CAPACITY points evicted the very entry it was
# about to reuse — the scenario's graphs/tables are the HOTTEST entries
# but also the OLDEST, so every sweep iteration rebuilt them (thrash).
#
# Lock-guarded get-or-build: batched BO prices several hardware points
# from worker threads, and a concurrent miss must not hand two threads two
# distinct CostTables objects for the same key — table *identity* is the
# device-buffer cache key one level up, so duplicate identities would
# duplicate device uploads (and an unguarded popitem could corrupt the
# OrderedDict outright).


_GRAPH_CACHE: "OrderedDict" = OrderedDict()
_TABLE_CACHE: "OrderedDict" = OrderedDict()
_CACHE_CAPACITY = 256
_CACHE_LOCK = threading.Lock()
_STATS = {"graph_hits": 0, "graph_misses": 0,
          "table_hits": 0, "table_misses": 0}


def _graph_key(spec, batch, micro_batch, tp, n_blocks):
    return (spec, tuple(batch), int(micro_batch), int(tp), n_blocks)


def get_execution_graph(spec, batch, micro_batch, tp, n_blocks=None):
    """Cached ``build_execution_graph`` (the graph is pure data)."""
    from .workload import build_execution_graph

    key = _graph_key(spec, batch, micro_batch, tp, n_blocks)
    with _CACHE_LOCK:
        g = _GRAPH_CACHE.get(key)
        if g is None:
            _STATS["graph_misses"] += 1
            if len(_GRAPH_CACHE) >= _CACHE_CAPACITY:
                _GRAPH_CACHE.popitem(last=False)         # LRU eviction
            g = build_execution_graph(spec, list(batch), micro_batch, tp=tp,
                                      n_blocks=n_blocks)
            _GRAPH_CACHE[key] = g
        else:
            _STATS["graph_hits"] += 1
            _GRAPH_CACHE.move_to_end(key)                # refresh hot entry
    return g


def get_cost_tables(graph, graph_key, hw):
    """Cached ``CostTables.build``; the table key adds only the chiplet
    spec (tables are layout/bandwidth independent)."""
    from .evaluator import CostTables

    key = (graph_key, hw.spec_name)
    with _CACHE_LOCK:
        t = _TABLE_CACHE.get(key)
        if t is None:
            _STATS["table_misses"] += 1
            if len(_TABLE_CACHE) >= _CACHE_CAPACITY:
                _TABLE_CACHE.popitem(last=False)         # LRU eviction
            t = CostTables.build(graph, hw)
            _TABLE_CACHE[key] = t
        else:
            _STATS["table_hits"] += 1
            _TABLE_CACHE.move_to_end(key)                # refresh hot entry
    return t


def get_graph_and_tables(spec, batch, hw, micro_batch, n_blocks=None):
    """The search_mapping entry point: one cached (graph, tables) pair per
    (workload batch, micro-batch, TP, block window, chiplet spec)."""
    key = _graph_key(spec, batch, micro_batch, hw.tensor_parallel, n_blocks)
    g = get_execution_graph(spec, batch, micro_batch, hw.tensor_parallel,
                            n_blocks)
    return g, get_cost_tables(g, key, hw)


def cost_cache_stats() -> dict:
    with _CACHE_LOCK:
        return dict(_STATS, graphs=len(_GRAPH_CACHE),
                    tables=len(_TABLE_CACHE),
                    table_host_bytes=sum(t.nbytes
                                         for t in _TABLE_CACHE.values()))


def clear_cost_caches() -> None:
    with _CACHE_LOCK:
        _GRAPH_CACHE.clear()
        _TABLE_CACHE.clear()
        for k in _STATS:
            _STATS[k] = 0
