"""Host time of the strawman baseline build per request, in ms.

The ``repro.grid.baseline`` spans around the strawman instances built for
every cell in `repro.core.api._plan_grid` (their timing is not in it),
summed over the traced window on the profiler's clock and divided by the
requests served.  Nothing to read where the span did not run."""

import host_spans

SPAN = "repro.grid.baseline"


def read(ctx):
    return host_spans.per_request_ms(ctx, SPAN)
