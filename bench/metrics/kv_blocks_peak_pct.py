"""Peak share of the paged KV pool's blocks in use during the serve, in %
(the allocator's ``peak_used / capacity``)."""


def read(rec):
    c = rec.get("counters") or {}
    if not c.get("blocks_capacity"):
        return None
    return 100.0 * c["blocks_peak_used"] / c["blocks_capacity"]
