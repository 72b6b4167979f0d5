"""Block cache (``serve/cache``): MB copied into partition blocks on
misses, bypassing blocks included, per point request served
(``ServeStats.cache_load_bytes``)."""


def read(run):
    stats = run.layer.get("serve_stats")
    loaded = getattr(stats, "cache_load_bytes", None)
    if loaded is None or not stats.n_point:
        return None
    return loaded / 1e6 / stats.n_point
