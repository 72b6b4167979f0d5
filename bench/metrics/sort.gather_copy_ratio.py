"""Sort executor (``core/executor``), its epilogue: the record and key
bytes it wrote to build the sorted blocks (counter
``sort.gather_bytes``, ``SortStats.counters``) over the input bytes,
summed over the window's jobs.  One copy of each 100-byte record and
one gather of its 10-byte key read 1.1; a touch-up that reorders
gathers the keys once more."""

COUNTER = "sort.gather_bytes"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs:
        return None
    counters = [getattr(s, "counters", None) for s in jobs]
    if None in counters or any(COUNTER not in c for c in counters):
        return None  # a program that does not count the epilogue's bytes
    total = sum(s.input_bytes for s in jobs)
    return sum(c[COUNTER] for c in counters) / total if total else None
