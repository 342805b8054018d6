"""Share of the chip's peak that the whole search reaches, in %: the least
time for all evaluation work of the window over the window's wall time."""


def read(rec):
    if not rec.get("calls"):
        return None
    return 100.0 * rec["least_time_s"] / rec["window_s"]
