"""Host time of instance packing for the timing backend per request, in ms.

The ``repro.ir.pack`` spans around ``pack_instances`` in
`repro.core.ir.engine.batch_evaluate` (padding and packing; a grid
request packs its plans and its baseline), summed over the traced window
on the profiler's clock and divided by the requests served.  Nothing to
read where the span did not run."""

import host_spans

SPAN = "repro.ir.pack"


def read(ctx):
    return host_spans.per_request_ms(ctx, SPAN)
