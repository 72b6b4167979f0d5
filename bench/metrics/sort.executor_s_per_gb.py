"""Sort executor (``core/executor``: pack, dispatch, touch-up, gather):
its busy seconds per input GB over the window's jobs
(``SortStats.phase_seconds["sort"]``)."""


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or run.layer["input_gb"] <= 0:
        return None
    busy = [s.phase_seconds.get("sort") for s in jobs]
    if None in busy:
        return None
    return sum(busy) / run.layer["input_gb"]
