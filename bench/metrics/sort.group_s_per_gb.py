"""Partition phase (``core/stages/reader``), its grouping: the stable
argsort by bucket, the record ``take``, the bincount and the fragment
slices (span ``partition.group``), busy seconds per input GB summed over
the window's jobs (``SortStats.span_seconds``)."""

SPAN = "partition.group"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or run.layer["input_gb"] <= 0:
        return None
    spans = [getattr(s, "span_seconds", None) for s in jobs]
    if None in spans:  # a program without spans inside the sort
        return None
    return sum(sp.get(SPAN, 0.0) for sp in spans) / run.layer["input_gb"]
