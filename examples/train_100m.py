"""End-to-end training driver: ~100M-parameter LM with checkpoint/restart.

    PYTHONPATH=src python examples/train_100m.py --steps 300
    PYTHONPATH=src python examples/train_100m.py --steps 5 --preset tiny

Demonstrates the full stack: synthetic pipeline -> sharded train step
(grad accumulation, AdamW, clipping) -> atomic checkpoints -> resume.
Re-running the same command continues from the latest checkpoint.
"""

import argparse

import jax

from repro import use_compile_cache
from repro.configs.base import ArchConfig, ShapeCell
from repro.data.pipeline import SyntheticPipeline
from repro.models.common import param_count
from repro.models.lm import build_model
from repro.obs import get_logger
from repro.optim.adamw import AdamWConfig
from repro.sharding.rules import single_device_context
from repro.train.checkpoint import latest_step, restore_checkpoint
from repro.train.ft import run_with_restarts
from repro.train.loop import Trainer

log = get_logger("train_100m")

PRESETS = {
    # ~100M params: 12L x 640d, SwiGLU 2560, 10 heads, 32k vocab.
    "100m": ArchConfig(
        name="repro_100m",
        family="dense",
        n_layers=12,
        d_model=640,
        n_heads=10,
        n_kv_heads=10,
        d_ff=2560,
        vocab_size=32000,
        vocab_pad_multiple=64,
        tie_embeddings=True,
        attn_q_block=128,
        attn_kv_block=128,
    ),
    "tiny": ArchConfig(
        name="repro_tiny",
        family="dense",
        n_layers=2,
        d_model=128,
        n_heads=4,
        n_kv_heads=2,
        d_ff=512,
        vocab_size=1024,
        vocab_pad_multiple=64,
        tie_embeddings=True,
        attn_q_block=64,
        attn_kv_block=64,
    ),
}


def main() -> None:
    use_compile_cache()
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--preset", choices=PRESETS, default="100m")
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=256)
    parser.add_argument("--grad-accum", type=int, default=1)
    parser.add_argument("--ckpt-dir", default="artifacts/train_100m")
    args = parser.parse_args()

    cfg = PRESETS[args.preset]
    ctx = single_device_context()
    model = build_model(cfg, ctx)
    log.info(f"{cfg.name}: {param_count(model.specs) / 1e6:.1f}M parameters")
    cell = ShapeCell("train", "train", args.seq, args.batch)
    trainer = Trainer(
        model=model,
        cell=cell,
        opt_cfg=AdamWConfig(
            peak_lr=3e-4, warmup_steps=20, total_steps=args.steps
        ),
        grad_accum=args.grad_accum,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=25,
    )
    resumed = latest_step(args.ckpt_dir)
    if resumed is not None:
        log.info(f"resuming from checkpoint at step {resumed}")
    state, restarts = run_with_restarts(
        trainer,
        lambda: SyntheticPipeline(cfg, cell, seed=0),
        args.ckpt_dir,
        target_steps=args.steps,
    )
    # Report the tail of the loss curve.
    pipeline = SyntheticPipeline(cfg, cell, seed=0)
    state2, data_state = restore_checkpoint(args.ckpt_dir, model)
    pipeline.restore(data_state)
    _, history = trainer.run(state2, pipeline, n_steps=3, log_every=1)
    log.info(
        f"finished at step {int(state.step)} (restarts={restarts}); "
        f"latest losses: {[round(h['loss'], 4) for h in history]}"
    )


if __name__ == "__main__":
    main()
