"""Fault-tolerance harness: checkpoint/restart with failure injection.

``run_with_restarts`` drives training to ``target_steps``, restarting
from the latest checkpoint whenever the injected failure fires (or a real
exception escapes a step).  Because the data pipeline is stateless-
resumable and checkpoints are atomic, an interrupted run converges to a
bitwise-identical state as an uninterrupted one -- asserted by
tests/test_train_ft.py.

Straggler mitigation lives at two levels (DESIGN.md section 4): the SWOT
scheduler reroutes per-plane volume splits around degraded optical links
(`plane_bandwidth_scale`), and host failures fall back to this
checkpoint-restart path (optionally onto a smaller mesh -- elastic).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax

from repro.train.checkpoint import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.train.loop import TrainState, Trainer, init_train_state


class InjectedFailure(RuntimeError):
    """Simulated preemption/node loss."""


@dataclasses.dataclass
class FailurePlan:
    """Fail once when reaching each listed step (before checkpointing)."""

    at_steps: tuple[int, ...] = ()
    fired: set = dataclasses.field(default_factory=set)

    def maybe_fail(self, step: int) -> None:
        if step in self.at_steps and step not in self.fired:
            self.fired.add(step)
            raise InjectedFailure(f"injected failure at step {step}")


def run_with_restarts(
    trainer: Trainer,
    make_pipeline: Callable[[], object],
    checkpoint_dir: str,
    target_steps: int,
    seed: int = 0,
    failure_plan: FailurePlan | None = None,
    max_restarts: int = 10,
) -> tuple[TrainState, int]:
    """Train to ``target_steps`` surviving failures; returns (state,
    number_of_restarts)."""
    failure_plan = failure_plan or FailurePlan()
    trainer.checkpoint_dir = checkpoint_dir
    restarts = 0
    while True:
        pipeline = make_pipeline()
        if latest_step(checkpoint_dir) is not None:
            state, data_state = restore_checkpoint(
                checkpoint_dir, trainer.model
            )
            pipeline.restore(data_state)
        else:
            state = init_train_state(
                trainer.model, jax.random.PRNGKey(seed)
            )
            save_checkpoint(checkpoint_dir, state, pipeline.state())
        try:
            while int(state.step) < target_steps:
                from repro.data.pipeline import shard_batch

                with jax.set_mesh(trainer.model.ctx.mesh):
                    batch = shard_batch(next(pipeline), trainer.model.ctx)
                    state, _metrics = trainer._jit(state, batch)
                step = int(state.step)
                if step % trainer.checkpoint_every == 0:
                    save_checkpoint(checkpoint_dir, state, pipeline.state())
                failure_plan.maybe_fail(step)
            # Final checkpoint so elastic resume sees the last step.
            save_checkpoint(checkpoint_dir, state, pipeline.state())
            return state, restarts
        except InjectedFailure:
            restarts += 1
            if restarts > max_restarts:
                raise
