"""Scheduler (``serve/scheduler``): the median wait of the run's
requests from admission to being taken into a batch
(``ServeStats.queue_wait_s``)."""


def read(run):
    stats = run.layer.get("serve_stats")
    wait = getattr(stats, "queue_wait_s", None)
    if not wait:
        return None
    return wait.percentile(50) * 1e3
