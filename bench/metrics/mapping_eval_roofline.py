"""Share of its roofline that the population evaluation reaches, in %: the
least time the chip needs for the evaluation work of the window's calls
(``bench/work.py``, counted from shapes, the same whichever timing backend
ran) over the device's busy time inside the harness's evaluator spans."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr.span_busy_s.get("bench.eval"):
        return None
    return 100.0 * rec["least_time_s"] / tr.span_busy_s["bench.eval"]
