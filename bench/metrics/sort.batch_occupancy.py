"""Sort executor: the share of the device batches' slots that held real
records, over the window's jobs (``SortStats.batch_occupancy``, each
job weighted by its records)."""


def read(run):
    jobs = [s for s in run.layer.get("jobs", ()) if s.batch_occupancy > 0]
    if not jobs:
        return None
    slots = sum(s.n_records / s.batch_occupancy for s in jobs)
    return 100.0 * sum(s.n_records for s in jobs) / slots
