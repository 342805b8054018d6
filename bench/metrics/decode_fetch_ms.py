"""Time of one decode step of the service in ``repro.serve.decode.fetch``,
in ms: the ``np.asarray`` of the step's tokens, which waits for the device
to finish the step; averaged over the window's steps."""
from bench import program


def read(rec):
    got = program.decode_steps(rec)
    if got is None:
        return None
    steps, fetch = got
    return 1e-6 * sum(fetch[s.sid] for s in steps) / len(steps)
