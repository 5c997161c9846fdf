"""Seconds to import the serving program and reach the chip
(``import repro.serving``, ``jax.devices()``)."""


def read(run):
    return run.setup["import_s"]
