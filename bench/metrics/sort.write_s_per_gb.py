"""Write stage (``core/stages/writer``): its busy seconds per input GB
over the window's jobs (``SortStats.phase_seconds["write"]``)."""


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or run.layer["input_gb"] <= 0:
        return None
    busy = [s.phase_seconds.get("write") for s in jobs]
    if None in busy:
        return None
    return sum(busy) / run.layer["input_gb"]
