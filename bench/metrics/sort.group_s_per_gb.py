"""Partition phase (``core/stages/reader``), its grouping
(``reader.group_fragments``, span ``partition.group``): the bincount, a
stable radix argsort of the 16-bit bucket ids, and one ``np.take`` per
fragment over the fixed-stride records (one copy of each record; line
blocks gather the batch, then copy each fragment out), busy seconds per
input GB summed over the window's jobs (``SortStats.span_seconds``)."""

SPAN = "partition.group"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or run.layer["input_gb"] <= 0:
        return None
    spans = [getattr(s, "span_seconds", None) for s in jobs]
    if None in spans:  # a program without spans inside the sort
        return None
    return sum(sp.get(SPAN, 0.0) for sp in spans) / run.layer["input_gb"]
