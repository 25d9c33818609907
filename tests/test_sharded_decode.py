"""Sharded flash-decoding (LSE merge) vs single-device oracle."""

import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.attention import decode_attention, sharded_decode_attention
    from repro.sharding.rules import make_auto_mesh

    mesh = make_auto_mesh((8,), ("data",))
    b, smax, hq, hkv, d = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d))
    k = jax.random.normal(ks[1], (b, smax, hkv, d))
    v = jax.random.normal(ks[2], (b, smax, hkv, d))
    lens = jnp.array([37, 64], jnp.int32)  # ragged validity

    ref = decode_attention(q, k, v, lens)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda *a: sharded_decode_attention(
            *a, mesh=mesh, axis="data"))(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("SHARDED_DECODE_OK")
    """
)


def test_sharded_decode_matches_oracle():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    result = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert result.returncode == 0, result.stderr[-3000:]
    assert "SHARDED_DECODE_OK" in result.stdout
