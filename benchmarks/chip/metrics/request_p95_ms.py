"""request_p95_ms: the 95th percentile, over every request of the window,
of the time from its send to its reply on the host."""
import numpy as np


def read(run):
    lat = run.record.latencies_s
    if not lat:
        return None
    return float(np.percentile(np.asarray(lat) * 1e3, 95))
