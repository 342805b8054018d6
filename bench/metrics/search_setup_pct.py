"""Share of the window a search spends setting up, in %: the program's
``repro.search.setup`` spans (execution graphs and cost tables of every
batch, and one population evaluator per structure group, with the upload of
its tables) inside the window, over the window."""
from bench import program


def read(rec):
    recs = program.spans(rec)
    if recs is None or "open" not in rec:
        return None
    return 100.0 * program.clipped_s(recs, "repro.search.setup", rec["open"],
                                     rec["close"]) / rec["window_s"]
