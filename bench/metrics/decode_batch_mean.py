"""Mean number of requests in the window's decode steps."""


def read(rec):
    calls = rec.get("decodes")
    return sum(c[2] for c in calls) / len(calls) if calls else None
