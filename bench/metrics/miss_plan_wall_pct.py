"""Share of the replay window spent planning plan-cache misses, in %:
the growth of `CacheStats.plan_wall_s` over the window, over the window."""


def read(ctx):
    c = ctx.counters
    if not c.get("window_s"):
        return None
    return 100.0 * c["plan_wall_s"] / c["window_s"]
