"""Share of its HBM roofline the Topology-Bypassing twin scan reaches, in %.

The least bytes one request's twin scan moves (`workcount`, with relay
twin rows) over the chip's HBM bandwidth is the least time it could take;
that over the twin program's (``jit_relay_run``) measured device time per
request is the share.  Nothing to read where the twin did not run under
its own name."""

import workcount
from reference import relay

PROGRAM = "jit_relay_run"


def read(ctx):
    p = ctx.trace["programs"].get(PROGRAM)
    if not p or not ctx.requests or not p["seconds"]:
        return None
    c = ctx.config
    steps = len(relay.steps(c["pattern"], c["n_nodes"], 1.0)[0])
    rows = workcount.candidate_rows(
        c["n_planes"], c["planner"]["max_enumerated_planes"], True
    )
    nbytes = workcount.fused_chain_scan_bytes(
        ctx.cells_per_request, steps, c["n_planes"], rows, True
    )
    least_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (p["seconds"] / ctx.requests)
