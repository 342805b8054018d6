"""Host-clock time of one evaluator call (one generation of one structure
group), ending on ready results, in ms: the mean over the window."""


def read(rec):
    calls = rec.get("calls")
    if not calls:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, *_ in calls) / len(calls)
