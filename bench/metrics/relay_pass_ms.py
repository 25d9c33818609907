"""Host time of the planner's Topology-Bypassing twin pass per request, in ms.

Every ``repro.relay.*`` span (`repro.core.greedy`: the twin's grid state
with its relay depth tables, its planning call, its materialization, and
the pick's two scoring passes), summed over the traced window on the
profiler's clock and divided by the requests served.  Nothing to read
where the twin pass wrote no span of its own."""

import host_spans

PREFIX = "repro.relay."


def read(ctx):
    spans = host_spans.of(ctx)
    twin = [s["seconds"] for n, s in spans.items() if n.startswith(PREFIX)]
    if not twin or not ctx.requests:
        return None
    return 1e3 * sum(twin) / ctx.requests
