"""Plan-cache hits over lookups in the replay window, in % (from the
growth of `CacheStats.hits` and `CacheStats.misses` over the window)."""


def read(ctx):
    c = ctx.counters
    lookups = c.get("cache_hits", 0) + c.get("cache_misses", 0)
    if not lookups:
        return None
    return 100.0 * c["cache_hits"] / lookups
