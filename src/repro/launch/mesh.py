"""Production meshes: 16x16 single-pod (256 chips) and 2x16x16 multi-pod.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import and
only then builds meshes.
"""

from __future__ import annotations

import jax

from repro.sharding.rules import MeshContext, make_auto_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def production_context(*, multi_pod: bool = False) -> MeshContext:
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    return MeshContext(mesh=mesh, dp_axes=dp_axes)
