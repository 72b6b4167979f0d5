"""Index predict (``serve/index``): the mean time of one batch's
position prediction, encode and RMI (span ``serve.predict``)."""


def read(run):
    stats = run.layer.get("serve_stats")
    counts = getattr(stats, "span_counts", None)
    if not counts or not counts.get("serve.predict"):
        return None
    return 1e3 * stats.span_seconds["serve.predict"] / counts["serve.predict"]
