"""Single read point for every ``REPRO_*`` environment knob.

The knobs were historically parsed ad hoc at each consumer
(`ir/backends.py`, `runtime/arbiter.py`, `obs/log.py`), each with its own
default literal and error message.  This module centralizes them: one
registry with the environment-variable name, type, default, and a short
description per knob, plus typed accessors that every consumer reads
through.  ``describe()`` dumps the registry with raw and effective values
for debugging (``python -m repro.core.knobs`` prints it).

Reads happen *per call* -- never cached at import -- so tests can
monkeypatch ``os.environ`` without reloading modules, exactly like the
scattered readers behaved before consolidation.

Defaults live here and nowhere else; consumers that need the numeric
default (e.g. docstrings) import the ``DEFAULT_*`` constants.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

# Environment-variable names (the public contract; referenced by CI and
# docs, so renaming any of these is a breaking change).
ENV_IR_BACKEND = "REPRO_IR_BACKEND"
ENV_ARBITER_BACKEND_THRESHOLD = "REPRO_ARBITER_BACKEND_THRESHOLD"
ENV_GRID_BACKEND_THRESHOLD = "REPRO_GRID_BACKEND_THRESHOLD"
ENV_FUSED_PLANNER_THRESHOLD = "REPRO_FUSED_PLANNER_THRESHOLD"
ENV_LOG = "REPRO_LOG"

# Defaults (single source of truth).
DEFAULT_IR_BACKEND = "numpy"
# Equals the arbiter's release-candidate cap (_MAX_RELEASE_CANDIDATES):
# exactly the maximum-size shrink batches flip to jax.  The arbiter
# asserts the invariant at import.
DEFAULT_ARBITER_BACKEND_THRESHOLD = 16
DEFAULT_GRID_BACKEND_THRESHOLD = 64
# Set on the CPU, where below it the fused scan's trace and compile did
# not pay for themselves; every grid keeps it but the CHAIN sweep grids
# below it on a TPU.
DEFAULT_FUSED_PLANNER_THRESHOLD = 256
# Those grids: on one TPU v5e the fused scan (program loaded) beat the
# per-step numpy loop about 10x at every size measured, from one cell
# (benchmarks/planner_crossover.py, PERF.md), so every such grid takes
# it.  The first grid of a power-of-two bucket in a process pays a
# compile or a cache read instead.  Not an environment knob;
# REPRO_FUSED_PLANNER_THRESHOLD overrides it too.
TPU_CHAIN_FUSED_PLANNER_THRESHOLD = 1
DEFAULT_LOG = ""  # "" = plain narrative rendering


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered environment knob."""

    env: str
    kind: str  # "str" | "int"
    default: Any
    doc: str

    def raw(self) -> str | None:
        """The raw environment value, or None when unset."""
        return os.environ.get(self.env)

    def value(self) -> Any:
        """The effective (parsed, defaulted) value.

        Raises ``ValueError`` naming the variable on a malformed int so
        a typo'd knob fails loudly instead of silently picking a default.
        """
        raw = self.raw()
        if raw is None or (self.kind == "int" and raw == ""):
            return self.default
        if self.kind == "int":
            try:
                return int(raw)
            except ValueError as exc:
                raise ValueError(
                    f"{self.env} must be an integer, got {raw!r}"
                ) from exc
        return raw


KNOBS: dict[str, Knob] = {
    k.env: k
    for k in (
        Knob(
            ENV_IR_BACKEND,
            "str",
            DEFAULT_IR_BACKEND,
            "process-wide default timing backend (numpy | jax | pallas)",
        ),
        Knob(
            ENV_ARBITER_BACKEND_THRESHOLD,
            "int",
            DEFAULT_ARBITER_BACKEND_THRESHOLD,
            "candidate-batch size at which the arbiter's lease "
            "re-scoring auto-selects jax (<= 0 disables)",
        ),
        Knob(
            ENV_GRID_BACKEND_THRESHOLD,
            "int",
            DEFAULT_GRID_BACKEND_THRESHOLD,
            "grid-cell count at which plan_grid/swot_greedy_grid "
            "auto-select the jax backend (<= 0 disables)",
        ),
        Knob(
            ENV_FUSED_PLANNER_THRESHOLD,
            "int",
            DEFAULT_FUSED_PLANNER_THRESHOLD,
            "grid-cell count at which the fused lax.scan planner is "
            "auto-selected, on every grid (unset: "
            f"{DEFAULT_FUSED_PLANNER_THRESHOLD}, smaller CHAIN sweep grids "
            f"on a TPU {TPU_CHAIN_FUSED_PLANNER_THRESHOLD}; <= 0 disables)",
        ),
        Knob(
            ENV_LOG,
            "str",
            DEFAULT_LOG,
            "narrative-log rendering: plain (default) | json | debug "
            "| quiet",
        ),
    )
}


# -- typed accessors (the consumer-facing API) ------------------------------
def ir_backend() -> str:
    """The process-wide default timing-backend name."""
    return KNOBS[ENV_IR_BACKEND].value()


def arbiter_backend_threshold() -> int:
    return KNOBS[ENV_ARBITER_BACKEND_THRESHOLD].value()


def grid_backend_threshold() -> int:
    return KNOBS[ENV_GRID_BACKEND_THRESHOLD].value()


def fused_planner_threshold(
    default: int = DEFAULT_FUSED_PLANNER_THRESHOLD,
) -> int:
    """``REPRO_FUSED_PLANNER_THRESHOLD`` where set, else ``default``."""
    return int_knob(ENV_FUSED_PLANNER_THRESHOLD, default)


def log_mode() -> str:
    """The normalized ``REPRO_LOG`` mode string (lowercased, stripped)."""
    return str(KNOBS[ENV_LOG].value()).strip().lower()


def int_knob(env: str, default: int) -> int:
    """Generic integer read for callers that pass the env name through
    (the shared ``select_backend_by_size`` policy takes the variable as a
    parameter).  Registered knobs keep their registry default unless the
    caller's ``default`` differs -- the caller wins, matching the legacy
    per-site parsing."""
    knob = KNOBS.get(env)
    if knob is not None and knob.default == default:
        return knob.value()
    return Knob(env, "int", default, "ad hoc").value()


def describe() -> dict[str, dict[str, Any]]:
    """Registry dump: per knob, the raw and effective values + default.

    For debugging ("why did this run pick jax?"): every entry shows
    whether the variable is set, what it parses to, and the documented
    default.  Malformed values surface as ``"<error: ...>"`` rather than
    raising, so a dump never fails.
    """
    out: dict[str, dict[str, Any]] = {}
    for env, knob in sorted(KNOBS.items()):
        try:
            effective: Any = knob.value()
        except ValueError as exc:
            effective = f"<error: {exc}>"
        out[env] = {
            "set": knob.raw() is not None,
            "raw": knob.raw(),
            "effective": effective,
            "default": knob.default,
            "doc": knob.doc,
        }
    return out


def _main() -> None:  # pragma: no cover - debugging CLI
    import json

    print(json.dumps(describe(), indent=2, default=str))


if __name__ == "__main__":  # pragma: no cover
    _main()
