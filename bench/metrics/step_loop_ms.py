"""Host time of the per-step numpy planner per request, in ms.

The ``repro.grid.step_loop`` spans around the per-step loop of
`repro.core.greedy` (``_chain_grid_chosen``; the INDEPENDENT loops
likewise), summed over the traced window on the profiler's clock and
divided by the requests served.  Nothing to read where the fused planner
ran instead."""

import host_spans

SPAN = "repro.grid.step_loop"


def read(ctx):
    return host_spans.per_request_ms(ctx, SPAN)
