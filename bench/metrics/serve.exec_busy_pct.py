"""Entry (``QueryServer``): the share of the server's life its one
execution thread spent executing batches (span ``serve.execute`` over
``ServeStats.wall_seconds``)."""


def read(run):
    stats = run.layer.get("serve_stats")
    spans = getattr(stats, "span_seconds", None)
    if spans is None or not stats.wall_seconds:
        return None
    return 100.0 * spans.get("serve.execute", 0.0) / stats.wall_seconds
