"""Share of the warm dispatches' host intervals (``bench.serve``) in
which no op ran on the device, over the traced part of the window."""

from devtrace import covered


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.marks_named("bench.serve")
    total = sum(e - s for s, e in spans)
    if total <= 0:
        return None
    busy = run.trace.busy()
    return 1.0 - sum(covered(busy, s, e) for s, e in spans) / total
