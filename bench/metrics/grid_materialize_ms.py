"""Host time of the Decisions materialization per request, in ms.

The ``repro.grid.materialize`` spans around the epilogue that turns the
planner's per-step tuples into split dicts, bypass routes and
``Decisions`` (`repro.core.greedy._chain_grid_decisions`), summed over
the traced window on the profiler's clock and divided by the requests
served.  Nothing to read where the span did not run."""

import host_spans

SPAN = "repro.grid.materialize"


def read(ctx):
    return host_spans.per_request_ms(ctx, SPAN)
