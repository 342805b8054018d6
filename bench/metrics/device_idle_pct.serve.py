"""Share of the traced window in which no operation ran on the device, in
% (serving cells)."""


def read(rec):
    tr = rec.get("trace")
    return None if tr is None else tr.idle_pct
