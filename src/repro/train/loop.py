"""Training loop: jitted train step, grad accumulation, SWOT planning.

``make_train_step`` builds the donated, sharding-annotated step function:

* microbatch gradient accumulation via ``lax.scan`` (collectives of one
  microbatch overlap the next microbatch's compute on real hardware);
* AdamW with clipping + warmup-cosine;
* optional int8 gradient compression (error-feedback state in TrainState);
* param/optimizer shardings from the rules engine (FSDP when configured).

``Trainer`` drives steps, checkpoints, and the SWOT shim: at startup it
profiles the step's collectives (`repro.core.planner`), installs schedules
(paper Phase 1), and reports the per-iteration optical timeline.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell
from repro.models.lm import Model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.sharding.rules import MeshContext, param_named_shardings

Pytree = Any


@dataclasses.dataclass
class TrainState:
    params: Pytree
    opt: dict
    step: jax.Array


def make_grad_fn(model: Model, grad_accum: int = 1):
    """(params, batch) -> (loss, metrics, grads) with microbatch accum."""

    def loss_of(params, batch):
        loss, metrics = model.loss_fn(params, batch)
        return loss, metrics

    def compute_grads(params, batch):
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params, batch)
            return loss, metrics, grads
        # Microbatch scan: batch leading dim splits into
        # (grad_accum, micro...); grads accumulate in f32.
        def micro(carry, mb):
            acc, loss_acc = carry
            (loss, _metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params, mb)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads
            )
            return (acc, loss_acc + loss), None

        micro_batch = jax.tree.map(
            lambda x: x.reshape(
                grad_accum, x.shape[0] // grad_accum, *x.shape[1:]
            ),
            batch,
        )
        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (grads, loss_sum), _ = jax.lax.scan(
            micro, (zero, jnp.zeros((), jnp.float32)), micro_batch
        )
        inv = 1.0 / grad_accum
        grads = jax.tree.map(lambda g: g * inv, grads)
        return loss_sum * inv, {}, grads

    return compute_grads


def make_train_step(
    model: Model,
    opt_cfg: AdamWConfig,
    grad_accum: int = 1,
):
    """Build (train_step, state_shardings) for jit."""
    cfg, ctx = model.cfg, model.ctx
    compute_grads = make_grad_fn(model, grad_accum)

    def train_step(state: TrainState, batch: dict):
        loss, metrics, grads = compute_grads(state.params, batch)
        new_params, new_opt, opt_metrics = adamw_update(
            grads, state.opt, state.params, opt_cfg
        )
        out_metrics = {"loss": loss, **metrics, **opt_metrics}
        return (
            TrainState(
                params=new_params, opt=new_opt, step=state.step + 1
            ),
            out_metrics,
        )

    param_sh = param_named_shardings(
        ctx, model.specs, fsdp=cfg.fsdp_params
    )
    opt_sh = {
        "m": param_sh,
        "v": param_sh,
        "count": NamedSharding(ctx.mesh, P()),
    }
    state_sh = TrainState(
        params=param_sh,
        opt=opt_sh,
        step=NamedSharding(ctx.mesh, P()),
    )
    return train_step, state_sh


def init_train_state(model: Model, key: jax.Array) -> TrainState:
    params = model.init(key)
    return TrainState(
        params=params,
        opt=adamw_init(params),
        step=jnp.zeros((), jnp.int32),
    )


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt", "step"], meta_fields=[]
)


@dataclasses.dataclass
class Trainer:
    """Step driver with checkpointing and SWOT optical planning."""

    model: Model
    cell: ShapeCell
    opt_cfg: AdamWConfig
    grad_accum: int = 1
    checkpoint_dir: str | None = None
    checkpoint_every: int = 50
    shim: Any = None  # repro.core.shim.SwotShim, optional
    recorder: Any = None  # repro.trace.TraceRecorder, optional

    def __post_init__(self):
        self._step_fn, self._state_sh = make_train_step(
            self.model, self.opt_cfg, self.grad_accum
        )
        self._jit = jax.jit(
            self._step_fn,
            donate_argnums=(0,),
            out_shardings=(self._state_sh, None),
        )

    def plan_optics(self, plan_ctx=None) -> str | None:
        """Phase 1: profile this step's collectives, install schedules.

        ``plan_ctx`` overrides the mesh context used for planning --
        e.g. plan for the 16x16 production mesh while executing locally
        (the planner only reads mesh *shapes*, so an AbstractMesh works).
        """
        if self.shim is None:
            return None
        from repro.core.planner import profile_train_step

        ctx = plan_ctx or self.model.ctx
        requests = profile_train_step(
            self.model.cfg, ctx, self.cell, self.model.specs
        )
        self.shim.install(requests)
        self._requests = requests
        return self.shim.iteration_report()

    def run(
        self,
        state: TrainState,
        pipeline,
        n_steps: int,
        log_every: int = 10,
    ) -> tuple[TrainState, list[dict]]:
        from repro.data.pipeline import shard_batch
        from repro.train.checkpoint import save_checkpoint

        history = []
        with jax.set_mesh(self.model.ctx.mesh):
            for _ in range(n_steps):
                batch = shard_batch(next(pipeline), self.model.ctx)
                t0 = time.perf_counter()
                state, metrics = self._jit(state, batch)
                if self.shim is not None:
                    for req in getattr(self, "_requests", []):
                        self.shim.intercept(req)
                        if self.recorder is not None:
                            self.recorder.record(req, phase="train")
                elif self.recorder is not None:
                    # No shim installed: record the Phase-1 profile
                    # directly so tracing does not require optics.
                    if not hasattr(self, "_requests"):
                        from repro.core.planner import profile_train_step

                        self._requests = profile_train_step(
                            self.model.cfg,
                            self.model.ctx,
                            self.cell,
                            self.model.specs,
                        )
                    for req in self._requests:
                        self.recorder.record(req, phase="train")
                if self.recorder is not None:
                    self.recorder.step_boundary()
                step = int(state.step)
                if step % log_every == 0 or step == 1:
                    loss = float(metrics["loss"])
                    history.append(
                        {
                            "step": step,
                            "loss": loss,
                            "wall_s": time.perf_counter() - t0,
                        }
                    )
                if (
                    self.checkpoint_dir
                    and step % self.checkpoint_every == 0
                ):
                    save_checkpoint(
                        self.checkpoint_dir, state, pipeline.state()
                    )
        return state, history
