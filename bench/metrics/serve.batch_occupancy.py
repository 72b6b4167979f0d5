"""Scheduler (``serve/scheduler``): the mean share of ``max_batch`` that
the window's batches filled (``ServeStats.batch_occupancy``)."""


def read(run):
    stats = run.layer.get("serve_stats")
    if stats is None or not stats.n_batches:
        return None
    return 100.0 * stats.batch_occupancy
