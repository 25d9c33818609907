"""HLO walker validation: scan-aware FLOPs/bytes/collective accounting."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo_text


def _compile_text(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _cost_analysis(compiled):
    cost = compiled.cost_analysis()
    return cost[0] if isinstance(cost, list) else cost  # old JAX: per-device list


def test_dot_flops_match_cost_analysis_loop_free():
    def f(x, w):
        return jnp.tanh(x @ w)

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    compiled, text = _compile_text(f, x, w)
    summary = analyze_hlo_text(text)
    xla_flops = _cost_analysis(compiled)["flops"]
    # Dot flops dominate; the walker must agree within 5%.
    assert summary.flops == pytest.approx(xla_flops, rel=0.05)


def test_scan_flops_scale_with_trip_count():
    def run_scan(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None

        y, _ = jax.lax.scan(body, x, ws)
        return y

    def run_unrolled(x, ws):
        for i in range(ws.shape[0]):
            x = jnp.tanh(x @ ws[i])
        return x

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    for n_layers in (3, 9):
        ws = jax.ShapeDtypeStruct((n_layers, 128, 128), jnp.float32)
        _, text_s = _compile_text(run_scan, x, ws)
        cu, _ = _compile_text(run_unrolled, x, ws)
        summary = analyze_hlo_text(text_s)
        unrolled_flops = _cost_analysis(cu)["flops"]
        # The walker recovers the trip count that cost_analysis drops.
        assert summary.flops == pytest.approx(unrolled_flops, rel=0.10), (
            n_layers,
            summary.flops,
            unrolled_flops,
        )
        assert n_layers in summary.while_trip_counts.values()


def test_nested_scan_multiplicities():
    def f(x, ws):
        def outer(c, wg):  # 4 groups
            def inner(ci, w):  # 3 layers each
                return jnp.tanh(ci @ w), None

            c2, _ = jax.lax.scan(inner, c, wg)
            return c2, None

        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 3, 64, 64), jnp.float32)
    _, text = _compile_text(f, x, ws)
    summary = analyze_hlo_text(text)
    # 12 total matmuls of 2*32*64*64 flops.
    expected = 12 * 2 * 32 * 64 * 64
    assert summary.flops == pytest.approx(expected, rel=0.10)


_COLLECTIVE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.analysis.hlo import analyze_hlo_text
    from repro.sharding.rules import make_auto_mesh

    mesh = make_auto_mesh((2, 4), ("data", "model"))

    def step(w, x):
        y = jnp.einsum("bd,df->bf", x, w)
        return jnp.sum(jnp.tanh(y))

    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 256), jnp.float32)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step,
            in_shardings=(NamedSharding(mesh, P(None, "model")),
                          NamedSharding(mesh, P("data", None))),
            out_shardings=NamedSharding(mesh, P())).lower(w, x).compile()
    s = analyze_hlo_text(compiled.as_text())
    assert s.collective_bytes > 0, "no collectives found"
    assert "all-reduce" in s.collective_by_kind, s.collective_by_kind
    print("COLLECTIVE_BYTES", s.collective_bytes)
    print("HLO_ANALYSIS_OK")

    # Scanned layers with a collective inside the body: bytes must scale
    # with the trip count.
    def layered(x, ws):
        def body(c, w):
            y = jnp.einsum("bd,df->bf", c, w)
            return jnp.tanh(y), None
        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y)

    for n in (2, 6):
        ws = jax.ShapeDtypeStruct((n, 256, 256), jnp.float32)
        x2 = jax.ShapeDtypeStruct((32, 256), jnp.float32)
        with jax.set_mesh(mesh):
            c = jax.jit(layered,
                in_shardings=(NamedSharding(mesh, P("data", None)),
                              NamedSharding(mesh, P(None, None, "model"))),
                out_shardings=NamedSharding(mesh, P())).lower(x2, ws).compile()
        summary = analyze_hlo_text(c.as_text())
        print("N", n, "COLL", summary.collective_bytes)
    print("SCALING_DONE")
    """
)


def test_collective_bytes_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    result = subprocess.run(
        [sys.executable, "-c", _COLLECTIVE_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert result.returncode == 0, result.stderr[-3000:]
    assert "HLO_ANALYSIS_OK" in result.stdout
    lines = [
        l for l in result.stdout.splitlines() if l.startswith("N ")
    ]
    # Collective bytes inside the scan body scale with the trip count.
    n2 = float(lines[0].split()[-1])
    n6 = float(lines[1].split()[-1])
    if n2 > 0:
        assert n6 == pytest.approx(3 * n2, rel=0.2), (n2, n6)
