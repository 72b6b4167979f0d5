"""Route (``core/terasort`` partition phase): the bytes its
``all_to_all`` calls carry, from their operand shapes and counted for
every capacity retry (counter ``route.exchange_bytes``,
``SortStats.counters``), per input record over the window's jobs.  The
least a route needs is 3 bytes a record on four chips
(``harness.route_shapes``)."""

COUNTER = "route.exchange_bytes"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or "n_dev" not in run.layer or not run.layer["records"]:
        return None
    counters = [getattr(s, "counters", None) or {} for s in jobs]
    if any(COUNTER not in c for c in counters):
        return None  # a program that does not count the route's bytes
    return sum(c[COUNTER] for c in counters) / run.layer["records"]
