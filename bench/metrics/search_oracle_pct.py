"""Share of the window a search spends pricing adopted mappings with the
numpy oracle, in %: the program's ``repro.search.oracle`` spans inside the
window, over the window."""
from bench import program


def read(rec):
    recs = program.spans(rec)
    if recs is None or "open" not in rec:
        return None
    return 100.0 * program.clipped_s(recs, "repro.search.oracle", rec["open"],
                                     rec["close"]) / rec["window_s"]
