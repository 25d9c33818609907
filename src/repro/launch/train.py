"""Training launcher: ``python -m repro.launch.train --arch <id>``.

On this CPU container the full production configs are exercised via the
dry-run (`repro.launch.dryrun`); this driver runs REAL training steps,
so it defaults to the reduced smoke variant of the chosen architecture
(``--full`` opts into the exact assigned config -- sized for TPU pods).

Wires the whole stack: config -> model -> SWOT optical planning (Phase 1
schedule install + per-iteration report) -> sharded train loop with
checkpoints and restart.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs.base import ShapeCell
from repro.configs.registry import ARCH_IDS, get_config, smoke_config
from repro.core import OpticalFabric, SwotShim, TPU_V5E_LINK_BANDWIDTH
from repro.data.pipeline import SyntheticPipeline
from repro.models.common import param_count
from repro.models.lm import build_model
from repro.optim.adamw import AdamWConfig
from repro.sharding.rules import single_device_context
from repro.train.checkpoint import latest_step
from repro.train.ft import run_with_restarts
from repro.train.loop import Trainer


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--arch", choices=ARCH_IDS, default="qwen3_4b")
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--grad-accum", type=int, default=1)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--ckpt-dir", default=None)
    parser.add_argument("--ckpt-every", type=int, default=25)
    parser.add_argument(
        "--full",
        action="store_true",
        help="exact assigned config (TPU-sized; CPU will be slow)",
    )
    parser.add_argument(
        "--plan-optics",
        action="store_true",
        help="run SWOT Phase-1 scheduling for this step's collectives",
    )
    args = parser.parse_args()

    cfg = get_config(args.arch) if args.full else smoke_config(args.arch)
    ctx = single_device_context()
    model = build_model(cfg, ctx)
    print(
        f"{cfg.name}: {param_count(model.specs) / 1e6:.1f}M params "
        f"({'full' if args.full else 'smoke'} config)"
    )
    cell = ShapeCell("train", "train", args.seq, args.batch)

    shim = None
    if args.plan_optics:
        shim = SwotShim(
            OpticalFabric(
                16, 4, bandwidth=TPU_V5E_LINK_BANDWIDTH, t_recfg=200e-6
            )
        )
    trainer = Trainer(
        model=model,
        cell=cell,
        opt_cfg=AdamWConfig(
            peak_lr=args.lr,
            warmup_steps=max(args.steps // 10, 1),
            total_steps=args.steps,
        ),
        grad_accum=args.grad_accum,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
        shim=shim,
    )
    if shim is not None:
        # Plan against the production mesh shapes (AbstractMesh: the
        # planner reads shapes only), independent of the local run mesh.
        from jax.sharding import AbstractMesh
        from repro.sharding.rules import MeshContext

        plan_ctx = MeshContext(
            mesh=AbstractMesh((16, 16), ("data", "model")),
            dp_axes=("data",),
        )
        report = trainer.plan_optics(plan_ctx)
        print("--- SWOT Phase-1 optical plan (16x16 production mesh) ---")
        print(report)

    if args.ckpt_dir:
        resumed = latest_step(args.ckpt_dir)
        if resumed is not None:
            print(f"resuming from step {resumed}")
        state, restarts = run_with_restarts(
            trainer,
            lambda: SyntheticPipeline(cfg, cell, seed=0),
            args.ckpt_dir,
            target_steps=args.steps,
        )
        print(f"done at step {int(state.step)} (restarts={restarts})")
    else:
        from repro.train.loop import init_train_state

        state = init_train_state(model, jax.random.PRNGKey(0))
        pipeline = SyntheticPipeline(cfg, cell, seed=0)
        state, history = trainer.run(
            state, pipeline, n_steps=args.steps, log_every=10
        )
        for h in history:
            print(f"step {h['step']:4d} loss {h['loss']:.4f}")


if __name__ == "__main__":
    main()
