"""Host time of the grid state build per request, in ms.

The ``repro.grid.state`` spans around each ``_GridState`` construction in
`repro.core.greedy.swot_greedy_grid` (grid tables, reserve-set
candidates), summed over the traced window on the profiler's clock and
divided by the requests served.  Nothing to read where the span did not
run."""

import host_spans

SPAN = "repro.grid.state"


def read(ctx):
    return host_spans.per_request_ms(ctx, SPAN)
