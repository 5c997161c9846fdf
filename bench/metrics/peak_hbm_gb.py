"""The device's ``peak_bytes_in_use`` once the window has closed, before
the reference runs: the most HBM the process held."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
