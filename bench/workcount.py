"""The least bytes a planning kernel must move for one request.

Counted from the problem's sizes alone -- cells, steps, planes and
candidate reserve rows -- with every value at the float64 width of the
problem (8 bytes) and every flag at one byte.  The count is what any
implementation of the kernel has to read and write once; it does not
depend on the program's dtypes, padding or buckets, so a roofline share
read against it means the same whatever implements the kernel.

No operation count is given: the kernels run float64, for which the chip
publishes no peak, so their rooflines are taken against HBM bandwidth.
"""

from __future__ import annotations

VALUE = 8  # bytes of one float64 value or int64 id
FLAG = 1


def candidate_rows(planes: int, max_enumerated_planes: int, bypass: bool) -> int:
    """Candidate reserve rows per cell and step: every proper subset of the
    planes where they are few enough to enumerate, else the 0..3
    soonest-free prefixes; bypass doubles them with relay twins."""
    rows = (2**planes - 1) if planes <= max_enumerated_planes else 4
    return 2 * rows if bypass else rows


def fused_chain_scan_bytes(
    cells: int, steps: int, planes: int, cand_rows: int, bypass: bool
) -> int:
    """One CHAIN grid plan: per cell, its step configs and volumes, plane
    bandwidths, delay and starting state, and its candidate table (one flag
    per row and plane) in; per step, the chosen split per plane (and relay
    depth per plane with bypass) and a feasibility flag out."""
    tables = cells * (2 * steps * VALUE + 3 * planes * VALUE + VALUE)
    tables += cells * cand_rows * planes * FLAG
    per_step = planes * VALUE * (2 if bypass else 1) + FLAG
    return tables + cells * steps * per_step


def timing_scan_bytes(instances: int, steps: int, planes: int) -> int:
    """Earliest-start timing of ``instances`` plans: per instance, the
    split per step and plane, the step volumes and configs, the plane
    bandwidths and starting configs in; CCT, reconfiguration count,
    busy time per plane and two flags out."""
    inputs = instances * (steps * planes * VALUE + 2 * steps * VALUE + 2 * planes * VALUE)
    outputs = instances * (2 * VALUE + planes * VALUE + 2 * FLAG)
    return inputs + outputs
