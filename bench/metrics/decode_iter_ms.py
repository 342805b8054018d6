"""Mean host-clock time of the window's decode calls into the service (one
``paged_decode`` step of the whole batch, with its gather of the dense cache
view, ending on the tokens), in ms."""


def read(rec):
    calls = rec.get("decodes")
    return 1e3 * sum(t1 - t0 for t0, t1, *_ in calls) / len(calls) \
        if calls else None
