"""Mean of dispatch start minus due time over the window's requests:
the wait behind earlier requests on the serial pool."""


def read(run):
    recs = run.window.records
    if not recs:
        return None
    return sum(r["start"] - r["due"] for r in recs) / len(recs) * 1e3
