"""Static trace extraction: ArchConfig + mesh -> ``CollectiveTrace``.

No devices and no compilation: the mesh is a ``jax.sharding.AbstractMesh``
(`jax.sharding.AbstractMesh`), model parameter shapes come
from the metadata-only spec builders (`repro.models.lm.build_model`), and
the per-step collective set is the Phase-1 sharding profile
(`repro.core.planner.profile_train_step` / ``profile_serve_step``) -- so
the extracted payloads match what the live shim would intercept exactly
(MoE capacity semantics included, see ``_moe_requests`` vs
`repro.models.moe`).

On top of the flat profile this module adds what a *trace* needs and a
profile does not carry:

* **dependency order** -- the training step's dataflow: TP activation
  syncs and MoE dispatches (forward/backward) precede the gradient
  reduction, which precedes the parameter all-gather / pod reduction;
* **pipeline point-to-point** -- ``gpipe_forward``'s per-tick
  ``lax.ppermute`` stage handoffs (`repro.train.pipeline`) as
  ``neighbor_exchange`` events, one per pipeline tick
  (``microbatches + stages - 1``);
* **cadence** -- steps repeat ``n_steps`` times at ``cadence`` seconds.
"""

from __future__ import annotations

from typing import Sequence

from repro.configs.base import ArchConfig, ShapeCell, shape_cell
from repro.configs.registry import get_config
from repro.core.planner import (
    _dp_gradient_requests,
    _moe_requests,
    _tp_activation_requests,
)
from repro.trace.records import CollectiveTrace, TraceEvent, request_to_event

_BF16 = 2


def _mesh_context(dp: int, tp: int, pod: int):
    from jax.sharding import AbstractMesh
    from repro.sharding.rules import MeshContext

    if pod >= 2:
        mesh = AbstractMesh((pod, dp, tp), ("pod", "data", "model"))
        return MeshContext(mesh, dp_axes=("pod", "data"))
    mesh = AbstractMesh((dp, tp), ("data", "model"))
    return MeshContext(mesh, dp_axes=("data",))


def _model_specs(cfg: ArchConfig, ctx):
    """Metadata-only parameter specs (shapes, no arrays)."""
    from repro.models.lm import build_model

    return build_model(cfg, ctx).specs


def _chain(events: list[TraceEvent]) -> list[TraceEvent]:
    """Re-dep a list as a linear chain (each event after the previous)."""
    import dataclasses

    return [
        dataclasses.replace(ev, deps=(i - 1,) if i else ())
        for i, ev in enumerate(events)
    ]


def _pipeline_events(
    cfg: ArchConfig,
    cell: ShapeCell,
    dp_size: int,
    stages: int,
    microbatches: int,
    first_index: int,
) -> list[TraceEvent]:
    """GPipe stage-handoff p2p as ``neighbor_exchange`` events.

    One microbatch's activation slab crosses the stage ring every
    pipeline tick; ``gpipe_forward`` runs ``microbatches + stages - 1``
    ticks.  Ticks depend on their predecessor (the handoff is the
    pipeline's serialization point).
    """
    micro_tokens = max(
        cell.global_batch // max(dp_size, 1), 1
    ) * cell.seq_len // max(microbatches, 1)
    act_bytes = float(max(micro_tokens, 1) * cfg.d_model * _BF16)
    n_ticks = microbatches + stages - 1
    return [
        TraceEvent(
            op="neighbor_exchange",
            payload_bytes=act_bytes,
            participants=stages,
            tag="pp_stage_handoff",
            deps=(first_index + t - 1,) if t else (),
            count=1,
            phase=cell.kind,
        )
        for t in range(n_ticks)
    ]


def static_trace(
    arch: str | ArchConfig,
    *,
    kind: str = "train",
    cell: ShapeCell | str | None = None,
    dp: int = 2,
    tp: int = 4,
    pod: int = 1,
    pipeline_stages: int = 0,
    pipeline_microbatches: int = 1,
    n_steps: int = 1,
    cadence: float = 0.0,
    specs=None,
) -> CollectiveTrace:
    """Extract one workload step's collective demand statically.

    ``arch`` is a registry id (``repro.configs.registry``) or a config.
    ``kind`` picks the step type: ``"train"`` (optimizer step: forward
    TP/MoE collectives, then backward, then gradient sync),
    ``"prefill"`` or ``"decode"`` (serving step: forward only).  ``cell``
    overrides the input-shape cell (a ``ShapeCell`` or a registered
    shape name); by default the first registry shape of matching kind is
    used.  ``dp`` / ``tp`` / ``pod`` set the abstract mesh;
    ``pipeline_stages >= 2`` adds GPipe stage-handoff p2p events.
    ``specs`` injects pre-built parameter specs (skips the model build);
    for training without jax available, the build is required.

    Dependency order (train): forward compute collectives (TP syncs, MoE
    dispatch) form a chain; the DP gradient reduction depends on the
    last of them; the FSDP parameter all-gather / pod reduction depends
    on the gradient reduction.
    """
    cfg = arch if isinstance(arch, ArchConfig) else get_config(arch)
    if kind not in ("train", "prefill", "decode"):
        raise ValueError(f"kind must be train/prefill/decode, got {kind!r}")
    if isinstance(cell, str):
        cell = shape_cell(cell)
    if cell is None:
        cell = next(c for c in _default_cells() if c.kind == kind)
    if cell.kind != kind:
        raise ValueError(
            f"cell {cell.name!r} is kind {cell.kind!r}, wanted {kind!r}"
        )
    ctx = _mesh_context(dp, tp, pod)

    events: list[TraceEvent] = []
    # Forward-pass (and, in training, backward-pass) compute collectives:
    # the per-layer TP syncs and the MoE EP dispatch.  They serialize
    # through the layer stack, so chain them.
    compute = [
        request_to_event(r, phase=kind)
        for r in (
            _tp_activation_requests(cfg, ctx, cell)
            + _moe_requests(cfg, ctx, cell)
        )
    ]
    events.extend(_chain(compute))
    if pipeline_stages >= 2:
        events.extend(
            _pipeline_events(
                cfg,
                cell,
                ctx.dp_size,
                pipeline_stages,
                max(pipeline_microbatches, 1),
                len(events),
            )
        )
    if kind == "train":
        import dataclasses

        if specs is None:
            specs = _model_specs(cfg, ctx)
        grad = [
            request_to_event(r, phase="train")
            for r in _dp_gradient_requests(cfg, ctx, specs)
        ]
        # The gradient reduction waits for the whole backward pass (the
        # last compute/pipeline event); FSDP param all-gather and pod
        # reduction wait for the (local) gradient reduction in turn.
        anchor = (len(events) - 1,) if events else ()
        for ev in grad:
            events.append(dataclasses.replace(ev, deps=anchor))
            anchor = (len(events) - 1,)
    return CollectiveTrace(
        model=cfg.name,
        source="static",
        events=tuple(events),
        cadence=cadence,
        n_steps=n_steps,
    )


def _default_cells() -> Sequence[ShapeCell]:
    from repro.configs.base import SHAPES

    return SHAPES
