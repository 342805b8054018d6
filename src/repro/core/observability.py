"""Unified cache observability for the evaluation stack.

Four persistent caches keep the Compass inner loop fast, each previously
reporting through its own entry point:

* the jitted-pass compile caches (``jax_evaluator.jit_cache_sizes``) —
  retraces are the classic silent GA slowdown;
* the device-resident stacked cost-table buffers
  (``jax_evaluator.device_table_cache_stats``) — the heaviest
  host->device uploads, replicated per mesh device under sharding;
* the host-side execution-graph / cost-table LRUs
  (``timing.cost_cache_stats``) — rebuild misses dominate BO sweeps;
* the timing-backend dispatch/fallback counters
  (``timing.timing_backend_stats``) — which pass-B path actually ran
  (``dense`` / ``pallas`` / ``fused`` / ``fused_host``) and every
  off-TPU reroute (``pallas->dense`` degradations, ``fused->host``),
  so a silently-degraded kernel selection is visible, not guessed.

:func:`cache_stats` merges all of them into one JSON-serialisable dict,
adding per-device resident-buffer bytes so table replication cost is
visible device by device. Benchmarks embed it in their output records;
use it whenever "why is the search slow / fat" comes up.
"""
from __future__ import annotations

from .. import telemetry
from . import timing


def cache_stats() -> dict:
    """One merged view of every persistent cache in the evaluation stack.

    Keys: ``cost_tables`` (host graph/table LRU hits/misses/entries and
    host-resident bytes), and — when JAX is importable — ``jit`` (compile
    cache sizes incl. the sharded wrappers), ``device_tables``
    (device-buffer cache hits/misses/entries), ``device_resident_bytes``
    (per-device bytes of the cached stacked buffers) plus its total.
    Degrades to the host-side stats alone when JAX is unavailable.
    Also carries a ``timing_backend`` section (per-backend pass-B
    dispatch counts plus off-TPU fallback reroutes, ``pallas->dense``,
    ``fused->host``) and a ``telemetry`` section: the process-wide
    counters of ``repro.telemetry`` (serving iterations, block residency,
    OOM/blocked admissions, the order cache's hits and misses, compiles
    while tracing) under ``counters``, and count, total and self time of
    every span recorded while tracing was on under ``spans``."""
    out: dict = {"cost_tables": timing.cost_cache_stats(),
                 "timing_backend": timing.timing_backend_stats(),
                 "telemetry": {"counters": telemetry.snapshot(),
                               "spans": telemetry.span_totals()}}
    try:
        from . import jax_evaluator
    except Exception:                           # pragma: no cover - no jax
        return out
    per_device = jax_evaluator.device_table_resident_bytes()
    out["jit"] = jax_evaluator.jit_cache_sizes()
    out["device_tables"] = jax_evaluator.device_table_cache_stats()
    out["device_resident_bytes"] = per_device
    out["device_resident_bytes_total"] = sum(per_device.values())
    return out
