"""MoE: shard_map dispatch vs dense oracle, single- and multi-device.

The multi-device case (real EP all_to_all over 8 host devices) must run in
a subprocess because XLA fixes the host device count at first init.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.common import init_params
from repro.models.moe import MoeDims, moe_ffn, moe_param_specs, moe_reference
from repro.sharding.rules import single_device_context


def _setup(key, t, d, f, e, k, ep, cf=8.0):
    dims = MoeDims.for_mesh(e, k, d, f, ep, capacity_factor=cf)
    specs = moe_param_specs(dims, fsdp_experts=False)
    params = init_params(specs, key)
    return dims, params


def test_single_device_matches_reference():
    ctx = single_device_context()
    t, d, f, e, k = 32, 16, 24, 6, 2
    dims, params = _setup(jax.random.PRNGKey(0), t, d, f, e, k, ep=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, d))
    with jax.set_mesh(ctx.mesh):
        y, aux, drop = jax.jit(
            lambda x, p: moe_ffn(
                x,
                p,
                dims,
                mesh=ctx.mesh,
                dp_axes=ctx.dp_axes,
                ep_axis="model",
            )
        )(x, params)
    # Generous capacity => no drops => exact match with the dense oracle.
    assert float(drop) == 0.0
    ref = moe_reference(x.reshape(-1, d), params, dims)
    np.testing.assert_allclose(
        np.asarray(y.reshape(-1, d)), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    assert np.isfinite(float(aux))


def test_padded_experts_never_routed():
    ctx = single_device_context()
    dims, params = _setup(jax.random.PRNGKey(2), 16, 8, 12, 3, 2, ep=4)
    assert dims.n_experts_padded == 4
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 8))
    with jax.set_mesh(ctx.mesh):
        y, _, drop = moe_ffn(
            x, params, dims, mesh=ctx.mesh, dp_axes=ctx.dp_axes,
            ep_axis="model",
        )
    ref = moe_reference(x.reshape(-1, 8), params, dims)
    np.testing.assert_allclose(
        np.asarray(y.reshape(-1, 8)), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_capacity_drops_tokens():
    ctx = single_device_context()
    dims, params = _setup(
        jax.random.PRNGKey(4), 64, 8, 12, 4, 2, ep=1, cf=0.25
    )
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 8))
    with jax.set_mesh(ctx.mesh):
        _, _, drop = moe_ffn(
            x, params, dims, mesh=ctx.mesh, dp_axes=ctx.dp_axes,
            ep_axis="model",
        )
    assert float(drop) > 0.1


_MULTI_DEVICE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.common import init_params
    from repro.models.moe import MoeDims, moe_ffn, moe_param_specs, moe_reference
    from repro.sharding.rules import MeshContext
    from repro.sharding.rules import make_auto_mesh

    mesh = make_auto_mesh((2, 4), ("data", "model"))
    ctx = MeshContext(mesh=mesh, dp_axes=("data",))
    d, f, e, k = 16, 24, 8, 2   # 8 experts over ep=4 -> 2 local experts
    dims = MoeDims.for_mesh(e, k, d, f, 4, capacity_factor=8.0)
    params = init_params(moe_param_specs(dims, False), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d))
    with jax.set_mesh(mesh):
        y, aux, drop = jax.jit(lambda x, p: moe_ffn(
            x, p, dims, mesh=mesh, dp_axes=("data",), ep_axis="model"
        ))(x, params)
    assert float(drop) == 0.0, f"unexpected drops: {float(drop)}"
    ref = moe_reference(x.reshape(-1, d), params, dims)
    np.testing.assert_allclose(
        np.asarray(y.reshape(-1, d)), np.asarray(ref), rtol=2e-4, atol=2e-4)

    # Token-sliced EP (Perf lever) must agree with the oracle too.
    with jax.set_mesh(mesh):
        y2, _, drop2 = jax.jit(lambda x, p: moe_ffn(
            x, p, dims, mesh=mesh, dp_axes=("data",), ep_axis="model",
            token_slice=True,
        ))(x, params)
    assert float(drop2) == 0.0
    np.testing.assert_allclose(
        np.asarray(y2.reshape(-1, d)), np.asarray(ref), rtol=2e-4, atol=2e-4)

    # Sequence-sharded fused SP+EP path (seq dim 8 % ep 4 == 0).
    with jax.set_mesh(mesh):
        y3, _, _ = jax.jit(lambda x, p: moe_ffn(
            x, p, dims, mesh=mesh, dp_axes=("data",), ep_axis="model",
            token_slice=True, seq_sharded=True,
        ))(x, params)
    np.testing.assert_allclose(
        np.asarray(y3.reshape(-1, d)), np.asarray(ref), rtol=2e-4, atol=2e-4)
    print("MULTIDEVICE_MOE_OK")
    """
)


def test_multidevice_ep_all_to_all_roundtrip():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    result = subprocess.run(
        [sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert result.returncode == 0, result.stderr[-3000:]
    assert "MULTIDEVICE_MOE_OK" in result.stdout
