"""Mean host-clock time of the serve's prefill-chunk calls into the service
up to the window's end, warm-up included (``paged_extend`` through its
bucket entry, ending on the token), in ms."""


def read(rec):
    calls = rec.get("prefills")
    return 1e3 * sum(t1 - t0 for t0, t1, *_ in calls) / len(calls) \
        if calls else None
