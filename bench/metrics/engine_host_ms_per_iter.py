"""Host time of one working iteration of the service's engine loop outside
its model calls, in ms: the program's ``repro.serve.iter`` span less its
``.prefill`` and ``.decode`` children, averaged over the iterations that
ran a decode step of the window. Admission, ``scheduler.plan``, token
bookkeeping, retirement and KV release take this time."""
from bench import program

STEPS = ("repro.serve.iter.prefill", "repro.serve.iter.decode")


def read(rec):
    got = program.decode_steps(rec)
    if got is None:
        return None
    recs = program.spans(rec)
    decode_sids = {s.parent for s in got[0]}       # their .iter.decode spans
    iter_sids = {r.parent for r in recs if r.sid in decode_sids}
    iters = [r for r in recs if r.sid in iter_sids]
    if not iters:
        return None
    inner = program.child_ns(recs, iters, STEPS)
    return 1e-6 * sum(r.t1_ns - r.t0_ns - inner[r.sid]
                      for r in iters) / len(iters)
