"""Multi-step collectives vs lax oracles (8 host devices, subprocess)."""

import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.comms import algorithms as alg
    from repro.sharding.rules import make_auto_mesh
    from repro.comms.compression import (
        compressed_all_reduce, compress_decompress, wire_bytes)

    mesh = make_auto_mesh((8,), ("x",))

    def run(body, x, out_specs=P("x")):
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=out_specs,
        ))(x)

    key = jax.random.PRNGKey(0)
    # --- AllReduce algorithms vs psum --------------------------------------
    x = jax.random.normal(key, (8, 3, 40))  # sharded dim 8 over axis x
    want = np.asarray(jax.jit(jax.shard_map(
        lambda v: lax.psum(v, "x"), mesh=mesh,
        in_specs=P("x"), out_specs=P("x")))(x))
    for name, fn in (("ring", alg.ring_all_reduce),
                     ("rabenseifner", alg.rabenseifner_all_reduce)):
        got = np.asarray(run(lambda v, fn=fn: fn(v, "x"), x))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=name)
        print(f"{name}_allreduce OK")

    # --- All-to-all algorithms vs lax.all_to_all ---------------------------
    y = jax.random.normal(key, (8, 8, 5))   # (ranks, chunks, payload)
    want = np.asarray(jax.jit(jax.shard_map(
        lambda v: lax.all_to_all(v, "x", split_axis=1, concat_axis=1,
                                 tiled=False),
        mesh=mesh, in_specs=P("x"), out_specs=P("x")))(y))
    for name, fn in (("pairwise", alg.pairwise_all_to_all),
                     ("bruck", alg.bruck_all_to_all)):
        got = np.asarray(run(lambda v, fn=fn: fn(v[0], "x")[None], y))
        np.testing.assert_allclose(
            got, want.reshape(got.shape), rtol=1e-5, atol=1e-5,
            err_msg=name)
        print(f"{name}_alltoall OK")

    # --- Hierarchical all-reduce on a 2D mesh ------------------------------
    mesh2 = make_auto_mesh((2, 4), ("pod", "data"))
    z = jax.random.normal(key, (8, 24))
    want = np.asarray(jax.jit(jax.shard_map(
        lambda v: lax.psum(v, ("pod", "data")), mesh=mesh2,
        in_specs=P(("pod", "data")), out_specs=P(("pod", "data"))))(z))
    got = np.asarray(jax.jit(jax.shard_map(
        lambda v: alg.hierarchical_all_reduce(v, "data", "pod"),
        mesh=mesh2, in_specs=P(("pod", "data")),
        out_specs=P(("pod", "data"))))(z))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    print("hierarchical_allreduce OK")

    # --- Compressed all-reduce: approximate mean + error feedback ----------
    g = jax.random.normal(key, (8, 8192)) * 0.01
    mean = np.asarray(g).mean(axis=0)
    def _comp(v):
        out, err = compressed_all_reduce(v[0], "x")
        return out[None], err[None]
    got_all, err = jax.jit(jax.shard_map(
        _comp, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x"))))(g)
    # The ring sum is replicated by construction: every rank agrees.
    np.testing.assert_allclose(np.asarray(got_all[0]),
                               np.asarray(got_all[7]), atol=1e-6)
    got = got_all[0]
    rel = np.abs(np.asarray(got) - mean).max() / (np.abs(mean).max() + 1e-9)
    assert rel < 0.05, f"compressed allreduce error too large: {rel}"
    assert wire_bytes(g[0]) < g[0].size * 2, "wire not smaller than bf16"
    # Error feedback: residual equals quantization error exactly.
    rt = compress_decompress(g[0])
    np.testing.assert_allclose(
        np.asarray(err[0]), np.asarray(g[0] - rt), atol=1e-6)
    print("compressed_allreduce OK")
    print("COMMS_OK")
    """
)


def test_comms_multidevice():
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
    assert "COMMS_OK" in result.stdout, result.stdout


def test_pattern_handoff_matches_step_counts():
    """The runtime collectives and the scheduler patterns agree on the
    number of communication steps (one ppermute per pattern step)."""
    from repro.comms.algorithms import pattern_for

    assert pattern_for("ring_all_reduce", 8, 1e6).n_steps == 14
    assert pattern_for("rabenseifner_all_reduce", 8, 1e6).n_steps == 6
    assert pattern_for("pairwise_all_to_all", 8, 1e6).n_steps == 7
    assert pattern_for("bruck_all_to_all", 8, 1e6).n_steps == 3
