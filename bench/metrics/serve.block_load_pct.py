"""Block cache (``serve/cache``): the share of the server's life spent
copying partition blocks on misses (span ``serve.block_load`` over
``ServeStats.wall_seconds``)."""


def read(run):
    stats = run.layer.get("serve_stats")
    spans = getattr(stats, "span_seconds", None)
    if spans is None or not stats.wall_seconds:
        return None
    return 100.0 * spans.get("serve.block_load", 0.0) / stats.wall_seconds
