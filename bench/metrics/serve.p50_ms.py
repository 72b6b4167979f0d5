"""Entry (``QueryServer``, from the client's side): the median of the
window's request latencies from their due times, a failed request
counting as the longest wait.  At four fifths of the knee the queue
amplifies every stall of the host, so its runs spread too widely for a
bound (PERF.md); it is reported here, from the traced runs."""

import numpy as np


def read(run):
    lat = run.layer.get("latency_s")
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 50)) * 1e3
