"""Program-wide telemetry: counters, spans and compile attribution.

Pure Python: JAX is imported only once tracing turns on.

**Counters** are always on. ``bump`` adds to a cumulative per-process
counter, ``high_water`` keeps a gauge's highest value (the ``peak_*``
names), ``snapshot`` reads them all and ``reset`` zeroes them. The
search's order cache, the async service, the paged allocator and the
dense engine publish here; ``repro.core.cache_stats()`` is the read view.

**Spans** name what the program is doing: one whole mapping search
(``repro.search`` and its ``.setup`` and ``.oracle``), one GA generation's
operators and scoring (``repro.ga.step``, ``repro.ga.score``), one
population evaluation (``repro.eval`` and its ``.orders``, ``.dispatch``
and ``.fetch``), one working serving iteration (``repro.serve.iter`` and
its ``.plan``, ``.prefill``, ``.decode`` and ``.retire``) and one model
call of the service (``repro.serve.prefill`` / ``repro.serve.decode`` and
their ``.stage``, ``.dispatch`` and ``.fetch``). ``span(name, **attrs)``
is a context manager:

* tracing off (the default): one global check, then the shared no-op
  ``NO_SPAN`` is returned — no allocation, clock read or lock;
* tracing on: the span records ``SpanRecord(name, parent, t0_ns, t1_ns,
  attrs, sid)`` in memory on the ``time.perf_counter_ns`` clock, nested
  under the innermost span open on its thread, and enters a
  ``jax.profiler.TraceAnnotation`` of the same name and attributes, so a
  profile shows it in the host plane on the device events' clock.

Tracing is on between ``enable()`` and ``disable()``, and while a JAX
profiler session collects (``jax.profiler.start_trace`` or a remote
capture): the search checks for one as each search starts and the service
at each iteration (``follow_profiler``), so a profile of either holds the
program's spans with no switch of its own. ``drain()`` returns the
recorded spans and forgets them; ``span_totals()`` keeps count, total and
self time by name.

**Compile attribution**: while tracing is on, every JAX lowering to MLIR
counts under ``compiles`` and under ``compiles.<innermost open span>``, so
a recompile names the step that caused it.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import NamedTuple

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_LOCK = threading.Lock()


def _zero() -> dict:
    return {
        # serving lifecycle
        "services_started": 0,
        "engine_runs": 0,
        "iterations": 0,
        # serving work
        "prefill_tokens": 0,
        "decode_tokens": 0,
        # KV blocks the decode steps attend over, and their dense views
        "decode.kv_blocks_live": 0,
        "decode.kv_blocks_dense": 0,
        # paged-cache residency
        "blocks_reserved": 0,
        "blocks_freed": 0,
        "oom_events": 0,
        "blocked_admissions": 0,
        "peak_blocks_used": 0,
        "peak_slots_used": 0,
        "peak_queue_depth": 0,
        # truncation / fairness
        "truncated_runs": 0,
        "unfinished_requests": 0,
        # the search's scheduled-order cache
        "eval.order_hits": 0,
        "eval.order_misses": 0,
        # JAX lowerings while tracing was on
        "compiles": 0,
    }


_COUNTERS = _zero()


def bump(name: str, n: int = 1) -> None:
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def high_water(name: str, value: int) -> None:
    with _LOCK:
        if value > _COUNTERS.get(name, 0):
            _COUNTERS[name] = value


def snapshot() -> dict:
    with _LOCK:
        return dict(_COUNTERS)


def reset() -> None:
    """Zero the counters and forget the recorded spans and their totals."""
    with _LOCK:
        _COUNTERS.clear()
        _COUNTERS.update(_zero())
        _RECORDS.clear()
        _TOTALS.clear()


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------


class SpanRecord(NamedTuple):
    name: str
    parent: int | None      # sid of the enclosing span, None at the top
    t0_ns: int              # time.perf_counter_ns
    t1_ns: int
    attrs: dict
    sid: int


_tracing = False            # the one check a span makes while tracing is off
_enabled = False            # enable() / disable()
_profiling = False          # a JAX profiler session collects
_annotation = None          # jax.profiler.TraceAnnotation, once tracing is on
_listening = False
_RECORDS: list = []
_TOTALS: dict = {}          # name -> [count, total_ns, self_ns]
_SIDS = itertools.count()
_LOCAL = threading.local()


def _stack() -> list:
    try:
        return _LOCAL.stack
    except AttributeError:
        _LOCAL.stack = []
        return _LOCAL.stack


class _NoSpan:
    """The span handed out while tracing is off: it does nothing and is
    falsy, so a call site can skip work that only feeds a span."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, _type, _value, _tb):
        return False

    def __bool__(self):
        return False

    def set(self, **_attrs):
        pass


NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("name", "attrs", "sid", "parent", "t0", "child_ns", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1].sid if stack else None
        self.sid = next(_SIDS)
        self.child_ns = 0
        self._ann = _annotation(self.name, **self.attrs)
        self._ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        stack = _stack()
        stack.pop()
        dur = t1 - self.t0
        if stack:
            stack[-1].child_ns += dur
        rec = SpanRecord(self.name, self.parent, self.t0, t1, self.attrs,
                         self.sid)
        with _LOCK:
            _RECORDS.append(rec)
            tot = _TOTALS.setdefault(self.name, [0, 0, 0])
            tot[0] += 1
            tot[1] += dur
            tot[2] += dur - self.child_ns
        return False

    def set(self, **attrs):
        """Attach attributes known only once the span's work is done."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)


def span(name: str, **attrs):
    """Context manager marking ``name`` (see the module docstring)."""
    if not _tracing:
        return NO_SPAN
    return _Span(name, attrs)


def _update() -> None:
    global _tracing, _annotation, _listening
    on = _enabled or _profiling
    if on and _annotation is None:
        import jax

        _annotation = jax.profiler.TraceAnnotation
    if on and not _listening:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    _tracing = on


def enable() -> None:
    """Turn tracing on until ``disable()``."""
    global _enabled
    _enabled = True
    _update()


def disable() -> None:
    """Turn tracing off, unless a JAX profiler session still collects."""
    global _enabled
    _enabled = False
    _update()


def follow_profiler() -> bool:
    """Trace while a JAX profiler session collects. Cheap (one call into
    the profiler), so long-running loops call it at coarse steps: each
    search, each serving iteration. Returns whether tracing is on."""
    global _profiling
    jax = sys.modules.get("jax")
    now = jax is not None and jax.profiler.TraceAnnotation.is_enabled()
    if now != _profiling:
        _profiling = now
        _update()
    return _tracing


def drain() -> list:
    """The spans recorded since the last drain, in the order they ended."""
    with _LOCK:
        out = list(_RECORDS)
        _RECORDS.clear()
    return out


def span_totals() -> dict:
    """{name: {"count", "total_ns", "self_ns"}} over every span recorded
    since the last ``reset``; self time leaves out the child spans."""
    with _LOCK:
        return {n: {"count": c, "total_ns": t, "self_ns": s}
                for n, (c, t, s) in _TOTALS.items()}


def _on_duration(event: str, _duration: float, **_kw) -> None:
    if _tracing and event == COMPILE_EVENT:
        stack = _stack()
        bump("compiles")
        if stack:
            bump(f"compiles.{stack[-1].name}")
