"""Host time of one evaluator call outside the wait for its results, in
ms: the program's ``repro.eval`` span less its ``repro.eval.fetch`` (the
``np.asarray`` of the outputs, which waits for the device), averaged over
the window's calls. The scheduled-order lookup, host-to-device staging and
the dispatch of the jitted pass take this time."""
from bench import program


def read(rec):
    got = program.search_evals(rec)
    if got is None:
        return None
    calls, fetch = got
    return 1e-6 * sum(c.t1_ns - c.t0_ns - fetch[c.sid]
                      for c in calls) / len(calls)
