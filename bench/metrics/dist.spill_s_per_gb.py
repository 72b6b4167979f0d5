"""Route (``core/terasort`` partition phase), each chunk's spill: per
chip, the sort of its routed row indices, the gather of their record
bytes and the write to its range file (span ``route.spill``): busy
seconds per input GB summed over the window's jobs
(``SortStats.span_seconds``)."""

SPAN = "route.spill"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or "n_dev" not in run.layer or run.layer["input_gb"] <= 0:
        return None
    spans = [getattr(s, "span_seconds", None) or {} for s in jobs]
    if any(SPAN not in sp for sp in spans):
        return None  # a program without spans inside the route
    return sum(sp[SPAN] for sp in spans) / run.layer["input_gb"]
