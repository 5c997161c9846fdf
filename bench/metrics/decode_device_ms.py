"""Mean device time of one decode program run (``decode_next``, one
token, told apart by ``devtrace.step_programs``)
in the traced part of the window."""

from devtrace import step_programs


def read(run):
    if run.trace is None:
        return None
    runs = step_programs(run.trace, run.window.records)["decode"]
    return sum(e - s for s, e in runs) / len(runs) * 1e3 if runs else None
