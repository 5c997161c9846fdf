"""``ServingEngine.cold_start_s`` of the process's first engine: the
profile pass's eager cold start."""


def read(run):
    return run.setup["first_coldstart_s"]
