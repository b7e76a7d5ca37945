"""request_p90_s: the 90th percentile of request latency over every
request due in the window, each timed on the benchmark's clock from when
it was due to when its answer reached the client; a request that failed
or was not done by the end of the drain counts as infinitely late."""
import numpy as np


def read(run):
    if run.entry != "service" or not run.window.units:
        return None
    lat = [u.latency if u.status == "completed" else np.inf
           for u in run.window.units]
    return float(np.percentile(lat, 90, method="linear"))
