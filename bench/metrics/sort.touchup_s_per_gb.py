"""Sort executor (``core/executor``), its touch-up: the memcmp order of
the key bytes past the 8-byte device prefix (span ``sort.touchup``), busy
seconds per input GB summed over the window's jobs
(``SortStats.span_seconds``)."""

SPAN = "sort.touchup"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or run.layer["input_gb"] <= 0:
        return None
    spans = [getattr(s, "span_seconds", None) for s in jobs]
    if None in spans:  # a program without spans inside the sort
        return None
    return sum(sp.get(SPAN, 0.0) for sp in spans) / run.layer["input_gb"]
