"""Share of the dense KV view that the window's decode steps attend over,
in %: per real lane, the blocks holding its context and the new position
(``kv_blocks_live``) over every block of its table (``kv_blocks_dense``),
summed over the decode steps of the traced window. Each step's
``repro.serve.decode.stage`` span carries the step's increments of the
program's counters ``decode.kv_blocks_live`` and ``decode.kv_blocks_dense``;
those counters total the whole process, warm-up included, and the harness
reads no counter at the window's edges, so the window's share is read from
the spans. A program without them gives nothing."""
from bench import program


def read(rec):
    got = program.decode_steps(rec)
    if got is None:
        return None
    sids = {s.sid for s in got[0]}
    live = dense = 0
    for r in program.spans(rec):
        if r.parent in sids and r.name == "repro.serve.decode.stage":
            live += r.attrs.get("kv_blocks_live", 0)
            dense += r.attrs.get("kv_blocks_dense", 0)
    return 100.0 * live / dense if dense else None
