"""95th percentile of request latency, from the time a request was due
to its response, over every request of the window (a failed request
counts at the window's end).  Per layer: the host's stalls of 0.1 s
and more move it too widely for a bound on one chip."""

import numpy as np


def read(run):
    lat = [(r["end"] if r["path"] != "failed" else run.window.seconds)
           - r["due"] for r in run.window.records]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
