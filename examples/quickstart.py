"""Quickstart: schedule a collective with SWOT, then train a tiny model.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax

from repro import use_compile_cache
from repro.configs.base import ShapeCell
from repro.configs.registry import smoke_config
from repro.core import (
    CollectiveRequest,
    OpticalFabric,
    SwotShim,
)
from repro.data.pipeline import SyntheticPipeline
from repro.models.lm import build_model
from repro.obs import get_logger
from repro.optim.adamw import AdamWConfig
from repro.sharding.rules import single_device_context
from repro.train.loop import Trainer, init_train_state

log = get_logger("quickstart")


def main() -> None:
    use_compile_cache()
    # --- 1. SWOT: schedule a collective on an optical fabric ------------
    log.info("=== SWOT optical scheduling ===")
    shim = SwotShim(OpticalFabric(n_nodes=16, n_planes=4))
    req = CollectiveRequest(
        "rabenseifner_allreduce", 16, 25e6, "dp_grad_sync"
    )
    shim.install([req])  # Phase 1: pre-configuration
    plan = shim.intercept(req)  # Phase 2: runtime interception
    log.info(plan.schedule.timeline())
    log.info(
        f"SWOT {plan.cct * 1e6:.0f}us vs strawman "
        f"{plan.strawman_cct * 1e6:.0f}us ({plan.vs_strawman:+.1%})\n"
    )

    # --- 2. Train a reduced model for a few steps ------------------------
    log.info("=== training (reduced qwen3 config, CPU) ===")
    ctx = single_device_context()
    cfg = smoke_config("qwen3_4b")
    model = build_model(cfg, ctx)
    cell = ShapeCell("quickstart", "train", 64, 4)
    trainer = Trainer(
        model=model,
        cell=cell,
        opt_cfg=AdamWConfig(peak_lr=1e-3, warmup_steps=5, total_steps=40),
    )
    state = init_train_state(model, jax.random.PRNGKey(0))
    pipeline = SyntheticPipeline(cfg, cell, seed=0)
    state, history = trainer.run(state, pipeline, n_steps=20, log_every=5)
    for h in history:
        log.info(f"step {h['step']:3d}  loss {h['loss']:.4f}")


if __name__ == "__main__":
    main()
