"""Host time of the fused planner's call per request, in ms.

The ``repro.fused.call`` spans around each ``fused_*_grid_chosen`` call
(`repro.core.ir.fused`: table upload, launch, the wait for the scan,
copy-out, unpack into per-step tuples), summed over the traced window on
the profiler's clock and divided by the requests served.  Minus
``fused_scan_ms`` it is what surrounds the scan.  Nothing to read where
the per-step planner ran instead."""

import host_spans

SPAN = "repro.fused.call"


def read(ctx):
    return host_spans.per_request_ms(ctx, SPAN)
