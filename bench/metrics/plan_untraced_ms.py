"""Host time of ``plan()`` that no program span covers, per request, in ms.

The harness's ``bench.request`` spans minus every ``repro.*`` span
(`repro.obs.trace.host_span`; the program's spans are flat and lie inside
the requests) over the traced window (`host_spans`), divided by the
requests served.  Nothing to read where the program has no ``repro.*``
span."""

import host_spans

REQUEST = "bench.request"
PREFIX = "repro."


def read(ctx):
    spans = host_spans.of(ctx)
    program = [s["seconds"] for n, s in spans.items() if n.startswith(PREFIX)]
    request = spans.get(REQUEST)
    if not program or not request or not ctx.requests:
        return None
    return 1e3 * (request["seconds"] - sum(program)) / ctx.requests
