"""Share of the scheduled orders that the evaluator's order cache held, in
%: hits over hits and misses of the program's ``repro.eval.orders`` spans
(one per evaluator call, carrying its own counts) inside the window."""
from bench import program


def read(rec):
    recs = program.spans(rec)
    if recs is None or "open" not in rec:
        return None
    calls = program.within(recs, "repro.eval.orders", rec["open"],
                           rec["close"])
    hits = sum(c.attrs.get("hits", 0) for c in calls)
    looked = hits + sum(c.attrs.get("misses", 0) for c in calls)
    return 100.0 * hits / looked if looked else None
