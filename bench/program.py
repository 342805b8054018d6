"""The program's own spans in a traced run, for the per-layer readers.

The program (``repro.telemetry``) records its spans while a JAX profiler
session collects, so a traced run holds those of its window in memory, on
the host clock the harness's own times use (``time.perf_counter``). The
readers of ``bench/metrics`` that read them go through :func:`spans`, which
drains them once per run and keeps them in the record. Against a program
without ``repro.telemetry``, or a run that recorded none, it gives None
and the metric is left out of the result line.
"""
from __future__ import annotations

KEY = "program_spans"


def spans(rec: dict):
    """The program's span records (``repro.telemetry.SpanRecord``) of the
    run, or None."""
    if KEY not in rec:
        try:
            from repro import telemetry
        except ImportError:
            rec[KEY] = None
        else:
            rec[KEY] = telemetry.drain()
    return rec[KEY] or None


def within(recs, name: str, lo_s: float, hi_s: float) -> list:
    """Spans named ``name`` that start and end inside [lo_s, hi_s]."""
    lo, hi = lo_s * 1e9, hi_s * 1e9
    return [r for r in recs if r.name == name and r.t0_ns >= lo
            and r.t1_ns <= hi]


def clipped_s(recs, name: str, lo_s: float, hi_s: float) -> float:
    """Time in spans named ``name`` inside [lo_s, hi_s], in seconds."""
    lo, hi = lo_s * 1e9, hi_s * 1e9
    return sum(max(0.0, min(r.t1_ns, hi) - max(r.t0_ns, lo))
               for r in recs if r.name == name) * 1e-9


def child_ns(recs, parents, names) -> dict:
    """{parent sid: ns in its direct children named one of ``names``}."""
    sids = {p.sid for p in parents}
    out = dict.fromkeys(sids, 0)
    for r in recs:
        if r.parent in sids and r.name in names:
            out[r.parent] += r.t1_ns - r.t0_ns
    return out


def search_evals(rec):
    """The window's evaluator calls (``repro.eval`` spans that start and end
    inside it, as the harness's own calls do) and the time of each in its
    ``repro.eval.fetch``; None without program spans."""
    recs = spans(rec)
    if recs is None or "open" not in rec:
        return None
    calls = within(recs, "repro.eval", rec["open"], rec["close"])
    if not calls:
        return None
    return calls, child_ns(recs, calls, ("repro.eval.fetch",))


def decode_steps(rec):
    """The window's decode steps of the service (``repro.serve.decode``
    spans inside the harness's decode calls of the window) and the time of
    each in its ``repro.serve.decode.fetch``; None without program spans."""
    recs = spans(rec)
    calls = rec.get("decodes")
    if recs is None or not calls:
        return None
    lo, hi = min(c[0] for c in calls), max(c[1] for c in calls)
    steps = within(recs, "repro.serve.decode", lo, hi)
    if not steps:
        return None
    return steps, child_ns(recs, steps, ("repro.serve.decode.fetch",))
