"""Host time of one generation's GA operators, in ms: the program's
``repro.ga.step`` span (selection, crossover, mutation and the legality
filter, scoring excluded), averaged over the window's generations."""
from bench import program


def read(rec):
    recs = program.spans(rec)
    if recs is None or "open" not in rec:
        return None
    steps = program.within(recs, "repro.ga.step", rec["open"], rec["close"])
    if not steps:
        return None
    return 1e-6 * sum(s.t1_ns - s.t0_ns for s in steps) / len(steps)
