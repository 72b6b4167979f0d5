"""Sort executor (``core/executor``), its handoff: the time its
generator is suspended while the sorter stage puts a sorted block on the
writers' queue, which is the writers' backpressure inside the ``sort``
phase (span ``sort.handoff``), seconds per input GB summed over the
window's jobs (``SortStats.span_seconds``)."""

SPAN = "sort.handoff"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or run.layer["input_gb"] <= 0:
        return None
    spans = [getattr(s, "span_seconds", None) for s in jobs]
    if None in spans:  # a program without spans inside the sort
        return None
    return sum(sp.get(SPAN, 0.0) for sp in spans) / run.layer["input_gb"]
