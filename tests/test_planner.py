"""Planner: train/serve-step collective profiles on production meshes."""

import jax
import pytest

from repro.configs.base import shape_cell
from repro.configs.registry import get_config
from repro.core.planner import profile_serve_step, profile_train_step
from repro.models.lm import build_model
from jax.sharding import AbstractMesh
from repro.sharding.rules import MeshContext


def _ctx(shape=(16, 16), axes=("data", "model"), dp=("data",)):
    return MeshContext(
        mesh=AbstractMesh(shape, axes), dp_axes=dp
    )


def _specs(cfg, ctx):
    from repro.models.lm import _decoder_specs

    return _decoder_specs(cfg, ctx)


class TestTrainProfiles:
    def test_moe_emits_all_expected_collectives(self):
        cfg = get_config("qwen2_moe_a2_7b")
        ctx = _ctx()
        reqs = profile_train_step(
            cfg, ctx, shape_cell("train_4k"), _specs(cfg, ctx)
        )
        algos = {r.algorithm for r in reqs}
        assert "pairwise_alltoall" in algos  # EP dispatch
        assert "rabenseifner_allreduce" in algos  # TP activations
        assert {"reduce_scatter", "all_gather"} <= algos  # FSDP grads
        assert all(r.size > 0 for r in reqs)
        assert all(r.n_nodes == 16 for r in reqs)

    def test_dense_no_moe_collectives(self):
        cfg = get_config("qwen3_4b")
        ctx = _ctx()
        reqs = profile_train_step(
            cfg, ctx, shape_cell("train_4k"), _specs(cfg, ctx)
        )
        assert all(r.algorithm != "pairwise_alltoall" for r in reqs)
        # Non-FSDP dense arch syncs grads with one allreduce.
        tags = {r.tag for r in reqs}
        assert "dp_grad_allreduce" in tags

    def test_multipod_adds_pod_level_sync(self):
        cfg = get_config("qwen3_4b")
        ctx = _ctx((2, 16, 16), ("pod", "data", "model"), ("pod", "data"))
        reqs = profile_train_step(
            cfg, ctx, shape_cell("train_4k"), _specs(cfg, ctx)
        )
        assert any(r.tag == "pod_grad_allreduce" for r in reqs)

    def test_token_slice_shrinks_a2a(self):
        cfg = get_config("qwen2_moe_a2_7b")
        ctx = _ctx()
        cell = shape_cell("train_4k")
        base = profile_train_step(cfg, ctx, cell, _specs(cfg, ctx))
        sliced_cfg = cfg.replace(moe_token_slice=True)
        sliced = profile_train_step(
            sliced_cfg, ctx, cell, _specs(sliced_cfg, ctx)
        )
        a2a = lambda rs: next(
            r.size for r in rs if r.algorithm == "pairwise_alltoall"
        )
        assert a2a(sliced) == pytest.approx(a2a(base) / 16, rel=0.01)

    def test_tiny_batch_never_zero_volume(self):
        """Regression: batch < dp_size must not produce 0-byte requests."""
        from repro.configs.base import ShapeCell
        from repro.configs.registry import smoke_config

        cfg = smoke_config("qwen2_moe_a2_7b")
        ctx = _ctx()
        reqs = profile_train_step(
            cfg, ctx, ShapeCell("t", "train", 64, 4), _specs(cfg, ctx)
        )
        assert reqs
        assert all(r.size > 0 for r in reqs)

    def test_serve_profile_has_no_grad_sync(self):
        cfg = get_config("qwen2_moe_a2_7b")
        ctx = _ctx()
        reqs = profile_serve_step(cfg, ctx, shape_cell("decode_32k"))
        assert all("grad" not in r.tag for r in reqs)


def test_all_profiles_schedulable():
    """Every profiled collective must produce a legal SWOT schedule."""
    from repro.core import (
        OpticalFabric,
        TPU_V5E_LINK_BANDWIDTH,
        SwotShim,
    )

    cfg = get_config("qwen2_moe_a2_7b")
    ctx = _ctx()
    reqs = profile_train_step(
        cfg, ctx, shape_cell("train_4k"), _specs(cfg, ctx)
    )
    shim = SwotShim(
        OpticalFabric(
            16, 4, bandwidth=TPU_V5E_LINK_BANDWIDTH, t_recfg=200e-6
        ),
        method="greedy",
    )
    shim.install(reqs)
    for plan in shim.plans:
        plan.schedule.validate()
        assert plan.cct >= plan.ideal_cct * (1 - 1e-9)
