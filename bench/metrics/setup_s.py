"""Seconds from the start of the process to the window's open: imports,
the chip, the profile pass (an eager cold start and its sample) and
the pool's set-up."""


def read(run):
    return run.setup["setup_s"]
