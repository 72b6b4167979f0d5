"""Partition phase (``core/stages/reader``), its spills: each flush of
a buffered fragment into its partition's spill, RAM or disk (span
``partition.spill``), busy seconds per input GB summed over the window's
jobs (``SortStats.span_seconds``)."""

SPAN = "partition.spill"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or run.layer["input_gb"] <= 0:
        return None
    spans = [getattr(s, "span_seconds", None) for s in jobs]
    if None in spans:  # a program without spans inside the sort
        return None
    return sum(sp.get(SPAN, 0.0) for sp in spans) / run.layer["input_gb"]
