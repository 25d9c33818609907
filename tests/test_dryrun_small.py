"""Dry-run machinery on a small (8-device) mesh, in-process-safe.

The full 512-device sweep runs via ``python -m repro.launch.dryrun``;
this test exercises the same lowering path (abstract params + rules
shardings + compile + roofline extraction) in a subprocess with 8 host
devices so the pytest suite covers it quickly.
"""

import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import ShapeCell
    from repro.configs.registry import smoke_config
    from repro.configs.inputs import input_specs
    from repro.analysis.hlo import analyze_hlo_text
    from repro.analysis.roofline import model_flops_for, roofline_from_summary
    from repro.launch.dryrun import _abstract, _abstract_batch, _step_and_inputs
    from repro.sharding.rules import MeshContext
    from repro.sharding.rules import make_auto_mesh

    mesh = make_auto_mesh((2, 4), ("data", "model"))
    ctx = MeshContext(mesh=mesh, dp_axes=("data",))

    for arch in ("qwen3_4b", "qwen2_moe_a2_7b", "mamba2_130m"):
        cfg = smoke_config(arch).replace(vocab_pad_multiple=8)
        for kind, cell in (
            ("train", ShapeCell("t", "train", 64, 8)),
            ("decode", ShapeCell("d", "decode", 64, 8)),
        ):
            # mirror dryrun's cell driver on the small mesh
            from repro.models.lm import build_model
            model = build_model(cfg, ctx)
            step_fn, inputs, model = _step_and_inputs(cfg, ctx, cell)
            with jax.set_mesh(mesh):
                lowered = jax.jit(step_fn, donate_argnums=(0,)).lower(*inputs)
                compiled = lowered.compile()
                mem = compiled.memory_analysis()
                summary = analyze_hlo_text(compiled.as_text())
            assert summary.flops > 0
            assert summary.bytes_accessed > 0
            if kind == "train":
                # DP gradient sync must appear as collectives.
                assert summary.collective_bytes > 0, (arch, kind)
            mf = model_flops_for(cfg, cell, model.specs)
            roof = roofline_from_summary(
                arch, cell, "test", 8, summary, mf)
            assert roof.bound_s > 0
            assert roof.dominant in ("compute", "memory", "collective")
            print(f"{arch} {kind} ok: {summary.merge_note()[:80]}")
    print("DRYRUN_SMALL_OK")
    """
)


def test_dryrun_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    result = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=1200,
    )
    assert result.returncode == 0, result.stderr[-4000:]
    assert "DRYRUN_SMALL_OK" in result.stdout


def test_sharding_rules_divisibility_fallback():
    """Heads that don't divide the model axis fall back to replication;
    divisible dims shard; compound dp axes respected."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax.sharding import AbstractMesh
    from repro.sharding.rules import MeshContext

    mesh = AbstractMesh((2, 4, 4), ("pod", "data", "model"))
    ctx = MeshContext(mesh=mesh, dp_axes=("pod", "data"))
    # 12 heads % 4 == 0 -> sharded; 6 heads % 4 != 0 -> replicated.
    assert ctx.spec_for((256, 12, 64), ("embed", "heads", "head_dim")) == P(
        None, "model"
    )
    assert ctx.spec_for((256, 6, 64), ("embed", "heads", "head_dim")) == P()
    # Batch maps to the compound dp axes when divisible (16 % 8 == 0).
    assert ctx.spec_for((16, 128), ("batch", None)) == P(("pod", "data"))
    # batch=1 (long_500k) cannot shard; kv_seq takes the model axis.
    spec = ctx.spec_for(
        (4, 1, 4096, 8, 128),
        ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
    )
    assert spec[2] == "model"
    assert spec[1] is None  # batch=1 unsharded


def test_fsdp_spec_adds_dp_axis():
    import jax
    from jax.sharding import PartitionSpec as P

    from jax.sharding import AbstractMesh
    from repro.sharding.rules import MeshContext, fsdp_spec

    mesh = AbstractMesh((4, 4), ("data", "model"))
    ctx = MeshContext(mesh=mesh, dp_axes=("data",))
    # Attention weights with non-divisible heads: replicated by base
    # rules, FSDP shards the largest divisible dim over data.
    spec = fsdp_spec(ctx, (48, 2560, 6, 128), ("layers", "embed", "heads", "head_dim"))
    assert spec == P(None, "data")
    # Already dp-sharded specs unchanged.
    spec = fsdp_spec(
        ctx, (16, 2560, 512), ("experts", "embed", "expert_ffn_fsdp")
    )
    assert spec == P("model", None, "data")