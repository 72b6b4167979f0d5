"""Entry (``QueryServer``, from the client's side): the 99th percentile of
the window's request latencies from their due times, a failed request
counting as the longest wait.  Too unsteady at this cell's request count
for a bound (PERF.md), so it is reported here, from the traced runs."""

import numpy as np


def read(run):
    lat = run.layer.get("latency_s")
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 99)) * 1e3
