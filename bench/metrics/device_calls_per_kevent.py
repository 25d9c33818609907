"""Device program runs per 1,000 engine events of the traced replay window
(every XLA program run in the trace counts once)."""


def read(ctx):
    runs = sum(p["runs"] for p in ctx.trace["programs"].values())
    if not ctx.counters.get("events"):
        return None
    return 1e3 * runs / ctx.counters["events"]
