"""Block cache (``serve/cache``): hits over lookups of partition blocks
in the window (``ServeStats.cache_hit_rate``)."""


def read(run):
    stats = run.layer.get("serve_stats")
    if stats is None or not (stats.cache_hits + stats.cache_misses):
        return None
    return 100.0 * stats.cache_hit_rate
