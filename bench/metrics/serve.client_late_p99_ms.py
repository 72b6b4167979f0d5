"""Load generator (``bench/harness/loadgen.py``): the 99th percentile of
how late the client sent a request after it was due, so that a starved
client is not read as a fast server."""

import numpy as np


def read(run):
    late = run.layer.get("client_late_s")
    if late is None or not np.isfinite(late).any():
        return None
    return float(np.nanpercentile(late, 99)) * 1e3
