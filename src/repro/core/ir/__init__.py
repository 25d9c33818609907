"""Array schedule IR: struct-of-arrays core + pluggable timing backends.

The package splits the pre-refactor ``repro.core.ir`` module in two:

* `repro.core.ir.engine`   -- the IR itself (``ScheduleIR``, lossless
  converters, vectorized legality, CCT reductions), the batched sweep
  packer, and the greedy's water-fill/rollout primitives.
* `repro.core.ir.backends` -- the per-step timing recurrence behind a
  backend interface: ``numpy`` (reference) and ``jax`` (jit + scan over
  power-of-two buckets); `x64`, the scoped 64-bit mode every device
  entry point runs under; and `route`, the one choice of planner,
  backend and padding a grid takes.
* `repro.core.ir.fused`    -- the fused on-device grid planner: the
  whole per-step greedy loop (`repro.core.greedy.swot_greedy_grid`) as
  one jitted ``lax.scan``, bitwise-identical to the per-step numpy
  planner.  Chosen by grid size, mode and device (`route`).

Every pre-refactor import (``from repro.core.ir import batch_evaluate``)
keeps working; ``batch_evaluate``/``evaluate_decisions`` gained a
``backend=`` parameter (default numpy).
"""

from repro.core.ir.backends import (
    BACKENDS,
    BackendUnavailable,
    JaxBackend,
    NumpyBackend,
    Route,
    TimingBackend,
    get_backend,
    resolve_backend,
    route,
    x64,
)
from repro.core.ir.engine import (
    _BIG,
    KIND_RECFG,
    KIND_XMIT,
    NO_CONFIG,
    BatchInstance,
    BatchResult,
    IRMetrics,
    ScheduleIR,
    _pack,
    batch_evaluate,
    evaluate_decisions,
    execute_ir,
    fabric_arrays,
    finalize_result,
    from_ir,
    pack_instances,
    rollout_batch,
    to_ir,
    validate_ir,
    waterfill_batch,
)

__all__ = [
    "BACKENDS",
    "BackendUnavailable",
    "BatchInstance",
    "BatchResult",
    "IRMetrics",
    "JaxBackend",
    "KIND_RECFG",
    "KIND_XMIT",
    "NO_CONFIG",
    "NumpyBackend",
    "Route",
    "ScheduleIR",
    "TimingBackend",
    "_BIG",
    "_pack",
    "batch_evaluate",
    "evaluate_decisions",
    "execute_ir",
    "fabric_arrays",
    "finalize_result",
    "from_ir",
    "get_backend",
    "pack_instances",
    "resolve_backend",
    "rollout_batch",
    "route",
    "to_ir",
    "validate_ir",
    "waterfill_batch",
    "x64",
]
