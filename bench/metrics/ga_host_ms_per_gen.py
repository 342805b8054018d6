"""Host time per GA generation outside the evaluator call, in ms: the
window's wall time less the time inside the harness's spans around
``evaluate_population``, over the generations (evaluator calls) the window
completed. GA operators, the group loop and dispatch take this time."""


def read(rec):
    calls = rec.get("calls")
    if not calls:
        return None
    inside = sum(t1 - t0 for t0, t1, *_ in calls)
    return 1e3 * (rec["window_s"] - inside) / len(calls)
