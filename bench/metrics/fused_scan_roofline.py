"""Share of its HBM roofline the fused CHAIN planning scan reaches, in %.

The least bytes one request's scan moves (`workcount`) over the chip's
HBM bandwidth is the least time it could take; that over the scan's
measured device time per request is the share.  Nothing to read where
the scan did not run."""

import workcount
from reference.patterns import steps_of


def read(ctx):
    p = ctx.trace["programs"].get("jit_run")
    if not p or not ctx.requests or not p["seconds"]:
        return None
    c = ctx.config
    steps = len(steps_of(c["pattern"], c["n_nodes"], 1.0)[0])
    bypass = c["planner"].get("bypass_depth", 0) >= 2
    rows = workcount.candidate_rows(
        c["n_planes"], c["planner"]["max_enumerated_planes"], bypass
    )
    nbytes = workcount.fused_chain_scan_bytes(
        ctx.cells_per_request, steps, c["n_planes"], rows, bypass
    )
    least_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (p["seconds"] / ctx.requests)
