"""Partition phase (``core/stages/reader``), its grouping: the record
bytes it wrote (counter ``partition.group_bytes``,
``SortStats.counters``) over the input bytes, summed over the window's
jobs.  One copy of each record reads 1; a whole-batch gather and then a
copy of each fragment reads 2."""

COUNTER = "partition.group_bytes"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs:
        return None
    counters = [getattr(s, "counters", None) for s in jobs]
    if None in counters or any(COUNTER not in c for c in counters):
        return None  # a program that does not count the grouping's bytes
    total = sum(s.input_bytes for s in jobs)
    return sum(c[COUNTER] for c in counters) / total if total else None
