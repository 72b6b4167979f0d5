"""Device: the share of the window in which no operation ran on the
chip, from the trace (1 - union of device-op intervals / window)."""


def read(run):
    if run.reduced is None or "jobs" not in run.layer:
        return None
    return 100.0 * run.reduced.idle_share
