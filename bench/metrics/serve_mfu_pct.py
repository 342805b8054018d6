"""Share of the chip's peak that the whole served step reaches, in %: the
model FLOPs of every token the window processed (prompt chunks and decoded
tokens, 2 per matrix parameter plus attention over the actual context;
``bench/work.py``) over the window times the peak."""


def read(rec):
    if not rec.get("flops"):
        return None
    return 100.0 * rec["flops"] / (rec["window_s"]
                                   * rec["peaks"]["flops_per_s"])
