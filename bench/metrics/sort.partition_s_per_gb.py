"""Partition phase (``core/stages/reader``): its wall seconds per input
GB, summed over the window's jobs (``SortStats.phase_wall_seconds``)."""


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or run.layer["input_gb"] <= 0:
        return None
    wall = [s.phase_wall_seconds.get("partition") for s in jobs]
    if None in wall:
        return None
    return sum(wall) / run.layer["input_gb"]
