"""Banded search (``serve/index``): the mean time of one batch's
last-mile search inside the error band, fallbacks included (span
``serve.search``)."""


def read(run):
    stats = run.layer.get("serve_stats")
    counts = getattr(stats, "span_counts", None)
    if not counts or not counts.get("serve.search"):
        return None
    return 1e3 * stats.span_seconds["serve.search"] / counts["serve.search"]
