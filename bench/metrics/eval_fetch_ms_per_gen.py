"""Time of one evaluator call in ``repro.eval.fetch``, in ms: the
``np.asarray`` of the jitted pass's outputs, which waits for the device to
finish the pass and copies the (B, P) prices to the host; averaged over the
window's calls."""
from bench import program


def read(rec):
    got = program.search_evals(rec)
    if got is None:
        return None
    calls, fetch = got
    return 1e-6 * sum(fetch[c.sid] for c in calls) / len(calls)
