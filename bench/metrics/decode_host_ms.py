"""Host time of one decode step of the service outside the wait for its
tokens, in ms: the program's ``repro.serve.decode`` span less its
``.fetch`` (the ``np.asarray`` of the tokens, which waits for the device),
averaged over the window's steps. Filling the transfer buffers, staging
them on the device and dispatching the entry point take this time."""
from bench import program


def read(rec):
    got = program.decode_steps(rec)
    if got is None:
        return None
    steps, fetch = got
    return 1e-6 * sum(s.t1_ns - s.t0_ns - fetch[s.sid]
                      for s in steps) / len(steps)
