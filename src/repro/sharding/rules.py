"""Logical-axis -> mesh-axis sharding rules with divisibility fallback.

Model code tags every parameter/activation dimension with a *logical axis*
(``ParamSpec.axes``).  This module maps logical axes to mesh axes via a
preference chain; a candidate mesh axis is taken only when the dimension
divides evenly by it and the axis is not already used in the same spec,
otherwise the chain falls through (usually to replication).  That keeps
every (arch x mesh) dry-run cell lowerable without GSPMD padding: e.g.
whisper's 12 heads or gemma's 8 q-heads on a 16-way model axis fall back
to replicated attention (Megatron-style "TP <= heads" rule), while their
FFN/vocab dims still shard 16 ways.

The special candidate ``DP`` expands to the (possibly compound) data-
parallel axes -- ``('data',)`` single-pod, ``('pod', 'data')`` multi-pod.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.common import ParamSpec, is_spec

DP = "DP"  # sentinel: the compound data-parallel axes

# Preference chains per logical axis.  First divisible unused candidate
# wins; empty chain or no fit => replicated.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    # Activations.
    "batch": (DP,),
    "seq_act": (),  # becomes ("model",) under sequence parallelism
    # Decode KV caches shard their sequence dim over 'model' (GSPMD then
    # emits flash-decoding-style partial attention + small stat
    # all-reduces); falls back to 'data' when model is taken and batch=1.
    "kv_seq": ("model", "data"),
    "embed": (),
    # Attention parameters.
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    # Dense FFN / embeddings.
    "mlp": ("model",),
    "vocab": ("model",),
    # MoE.
    "experts": ("model",),
    "experts_router": (),
    "expert_ffn": (),
    "expert_ffn_fsdp": (DP,),
    # Mamba2.
    "ssm_inner": ("model",),
    "ssm_heads": ("model",),
    "ssm_state": (),
    "ssm_conv_ch": (),
    # Stacking.
    "layers": (),
    "groups": (),
}


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """A mesh plus the roles of its axes and active rule overrides."""

    mesh: Mesh
    dp_axes: tuple[str, ...]  # ("data",) or ("pod", "data")
    tp_axis: str = "model"
    rules: dict[str, tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )

    def with_rules(self, **overrides: tuple[str, ...]) -> "MeshContext":
        merged = dict(self.rules)
        merged.update(overrides)
        return dataclasses.replace(self, rules=merged)

    @property
    def dp_size(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.dp_axes)

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    def _expand(self, candidate: str) -> tuple[str, ...]:
        return self.dp_axes if candidate == DP else (candidate,)

    def spec_for(
        self, shape: tuple[int, ...], axes: tuple[str | None, ...]
    ) -> P:
        """PartitionSpec for one array via the preference chains."""
        used: set[str] = set()
        entries: list[Any] = []
        for dim, logical in zip(shape, axes):
            choice: Any = None
            for cand in self.rules.get(logical or "", ()):
                mesh_axes = self._expand(cand)
                size = math.prod(self.mesh.shape[a] for a in mesh_axes)
                if size <= 1:
                    continue
                if any(a in used for a in mesh_axes):
                    continue
                if dim % size:
                    continue
                choice = mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]
                used.update(mesh_axes)
                break
            entries.append(choice)
        # Trim trailing Nones for readability (semantically identical).
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    def sharding_for(
        self, shape: tuple[int, ...], axes: tuple[str | None, ...]
    ) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(shape, axes))

    # -- Pytree-level helpers ---------------------------------------------
    def param_specs(self, spec_tree: Any) -> Any:
        """PartitionSpec tree for a ParamSpec tree."""
        return jax.tree.map(
            lambda s: self.spec_for(s.shape, s.axes),
            spec_tree,
            is_leaf=is_spec,
        )

    def param_shardings(self, spec_tree: Any) -> Any:
        return jax.tree.map(
            lambda s: self.sharding_for(s.shape, s.axes),
            spec_tree,
            is_leaf=is_spec,
        )

    def constrain(
        self, x: jax.Array, axes: tuple[str | None, ...]
    ) -> jax.Array:
        """with_sharding_constraint via logical axes."""
        return jax.lax.with_sharding_constraint(
            x, self.sharding_for(x.shape, axes)
        )

    @property
    def dp_spec(self) -> Any:
        """PartitionSpec entry for the batch dim."""
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]


def fsdp_spec(
    ctx: MeshContext,
    shape: tuple[int, ...],
    axes: tuple[str | None, ...],
) -> P:
    """Base spec plus data-axis sharding on one eligible dim (ZeRO/FSDP).

    Picks the largest not-yet-sharded, non-stacking dim divisible by the
    dp size; GSPMD then reduce-scatters gradients and keeps fp32
    optimizer state sharded over data, all-gathering weights per layer
    inside the scan body.
    """
    base = ctx.spec_for(shape, axes)
    dp = ctx.dp_size
    if dp <= 1:
        return base
    entries = list(base) + [None] * (len(shape) - len(base))
    used = {a for e in entries if e for a in (e if isinstance(e, tuple) else (e,))}
    if any(ax in used for ax in ctx.dp_axes):
        return base
    candidates = [
        (dim, i)
        for i, (dim, entry, logical) in enumerate(
            zip(shape, entries, axes)
        )
        if entry is None
        and logical not in ("layers", "groups")
        and dim % dp == 0
        and dim >= dp
    ]
    if not candidates:
        return base
    _, idx = max(candidates)
    entries[idx] = ctx.dp_spec
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_partition_specs(
    ctx: MeshContext, spec_tree: Any, fsdp: bool = False
) -> Any:
    fn = (
        (lambda s: fsdp_spec(ctx, s.shape, s.axes))
        if fsdp
        else (lambda s: ctx.spec_for(s.shape, s.axes))
    )
    return jax.tree.map(fn, spec_tree, is_leaf=is_spec)


def param_named_shardings(
    ctx: MeshContext, spec_tree: Any, fsdp: bool = False
) -> Any:
    return jax.tree.map(
        lambda p: NamedSharding(ctx.mesh, p),
        param_partition_specs(ctx, spec_tree, fsdp=fsdp),
        is_leaf=lambda x: isinstance(x, P),
    )


def make_auto_mesh(axis_shapes, axis_names) -> Mesh:
    """``jax.make_mesh`` with every axis Auto-sharded.

    ``jax.make_mesh`` defaults to Explicit axes; every mesh here relies on
    GSPMD propagation from the rule-derived specs, so all axes are Auto.
    """
    return jax.make_mesh(
        axis_shapes,
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


def single_device_context() -> MeshContext:
    """1x1 mesh for smoke tests and single-host runs."""
    mesh = make_auto_mesh((1, 1), ("data", "model"))
    return MeshContext(mesh=mesh, dp_axes=("data",))


def abstract_sharded_params(ctx: MeshContext, spec_tree: Any) -> Any:
    """ShapeDtypeStructs with shardings attached (for .lower dry-runs)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=ctx.sharding_for(s.shape, s.axes)
        ),
        spec_tree,
        is_leaf=is_spec,
    )
