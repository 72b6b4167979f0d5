"""Route (``core/terasort`` partition phase), the exchange of each
chunk: the sharded ``device_put``, the route program
(``jit_route_chunk``), its overflow check with any capacity retries,
and the fetch of the routed row indices (span ``route.exchange``): busy
seconds per input GB summed over the window's jobs
(``SortStats.span_seconds``)."""

SPAN = "route.exchange"


def read(run):
    jobs = run.layer.get("jobs")
    if not jobs or "n_dev" not in run.layer or run.layer["input_gb"] <= 0:
        return None
    spans = [getattr(s, "span_seconds", None) or {} for s in jobs]
    if any(SPAN not in sp for sp in spans):
        return None  # a program without spans inside the route
    return sum(sp[SPAN] for sp in spans) / run.layer["input_gb"]
