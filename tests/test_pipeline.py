"""GPipe pipeline parallelism: equivalence with sequential execution."""

import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.train.pipeline import gpipe_forward, gpipe_loss_fn, stack_stages
    from repro.sharding.rules import make_auto_mesh

    mesh = make_auto_mesh((4,), ("pipe",))
    L, D, M, MB = 8, 16, 6, 4   # 8 layers over 4 stages, 6 microbatches

    def layer_fn(w, h):
        return jnp.tanh(h @ w)

    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (L, D, D)) / jnp.sqrt(D)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))

    # Sequential reference.
    def sequential(ws, x):
        h = x.reshape(M * MB, D)
        for i in range(L):
            h = layer_fn(ws[i], h)
        return h.reshape(M, MB, D)

    ref = sequential(ws, x)
    staged = stack_stages(ws, 4)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, x: gpipe_forward(
            p, x, mesh=mesh, axis="pipe", layer_fn=layer_fn))(staged, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("forward OK")

    # Gradients through the pipeline == sequential gradients.
    y = jax.random.normal(jax.random.PRNGKey(2), (M, MB, D))
    loss = lambda o, t: jnp.mean((o - t) ** 2)

    def seq_loss(ws, x, y):
        return loss(sequential(ws, x), y)

    g_ref = jax.grad(seq_loss)(ws, x, y)
    with jax.set_mesh(mesh):
        g_pipe = jax.jit(jax.grad(lambda p, x, y: gpipe_loss_fn(
            p, x, y, mesh=mesh, axis="pipe",
            layer_fn=layer_fn, loss_fn=loss)))(staged, x, y)
    g_pipe = np.asarray(g_pipe).reshape(L, D, D)
    np.testing.assert_allclose(g_pipe, np.asarray(g_ref),
                               rtol=5e-5, atol=5e-5)
    print("grads OK")
    print("PIPELINE_OK")
    """
)


def test_gpipe_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    result = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900,
    )
    assert result.returncode == 0, result.stderr[-4000:]
    assert "PIPELINE_OK" in result.stdout


def test_stack_stages_shapes():
    import jax.numpy as jnp

    from repro.train.pipeline import stack_stages

    ws = {"w": jnp.zeros((8, 4, 4)), "b": jnp.zeros((8, 4))}
    staged = stack_stages(ws, 2)
    assert staged["w"].shape == (2, 4, 4, 4)
    assert staged["b"].shape == (2, 4, 4)

    import pytest

    with pytest.raises(ValueError):
        stack_stages({"w": jnp.zeros((7, 4))}, 2)
