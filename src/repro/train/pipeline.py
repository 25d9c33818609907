"""Pipeline parallelism: GPipe-style microbatch pipeline via shard_map.

Optional parallelism mode (DESIGN.md section 4): layer stacks split into
S stages along a mesh axis (e.g. the ``pod`` axis of the multi-pod
mesh); activations flow stage-to-stage with ``collective_permute`` while
M microbatches keep all stages busy (pipeline bubble = (S-1)/(M+S-1)).
Gradients come from ordinary jax autodiff through the shard_map program
(the transpose of ppermute is the reverse ppermute).

This module is self-contained over a user-provided ``layer_fn`` so it
composes with any homogeneous block stack; equivalence with sequential
execution is asserted in tests/test_pipeline.py.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


Pytree = object


def gpipe_forward(
    stage_params: Pytree,  # leaves (S, L_per_stage, ...) sharded on dim 0
    x: jax.Array,  # (M, mb, ...) microbatched inputs (replicated)
    *,
    mesh: jax.sharding.Mesh,
    axis: str,
    layer_fn: Callable,  # (layer_params, h) -> h
) -> jax.Array:
    """Run the pipelined stack; returns (M, mb, ...) final activations."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]

    def body(params_local, x_all):
        # params_local: (1, L, ...) -> (L, ...); x_all: (M, mb, ...).
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage = lax.axis_index(axis)
        mb_shape = x_all.shape[1:]
        state = jnp.zeros(mb_shape, x_all.dtype)
        outputs = jnp.zeros_like(x_all)
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def run_stage(h):
            def scan_body(c, lp):
                return layer_fn(lp, c), None

            h, _ = lax.scan(scan_body, h, params_local)
            return h

        for t in range(n_micro + n_stages - 1):
            # Stage 0 injects microbatch t; other stages use the handoff.
            if t < n_micro:
                inject = x_all[t]
            else:
                inject = jnp.zeros(mb_shape, x_all.dtype)
            h_in = jnp.where(stage == 0, inject, state)
            h_out = run_stage(h_in)
            # Last stage emits microbatch (t - S + 1) when valid.
            emit_idx = t - (n_stages - 1)
            if 0 <= emit_idx < n_micro:
                outputs = outputs.at[emit_idx].set(h_out)
            # Hand off to the next stage (ring-permute; stage S-1's
            # output wraps to stage 0 where it is ignored).
            state = lax.ppermute(h_out, axis, fwd_perm)
        # Only the last stage's rows are real; replicate them to all
        # stages (masked psum = broadcast from stage S-1).
        outputs = jnp.where(stage == n_stages - 1, outputs, 0)
        outputs = lax.psum(outputs, axis)
        return outputs

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(axis), stage_params),
            P(),
        ),
        out_specs=P(),
        check_vma=False,  # outputs are replicated by the final broadcast
    )(stage_params, x)


def stack_stages(params: Pytree, n_stages: int) -> Pytree:
    """Reshape stacked layer params (L, ...) -> (S, L/S, ...)."""

    def reshape(p):
        l = p.shape[0]
        if l % n_stages:
            raise ValueError(
                f"{l} layers not divisible into {n_stages} stages"
            )
        return p.reshape(n_stages, l // n_stages, *p.shape[1:])

    return jax.tree.map(reshape, params)


def gpipe_loss_fn(
    stage_params: Pytree,
    x: jax.Array,  # (M, mb, ...)
    targets: jax.Array,  # (M, mb, ...)
    *,
    mesh: jax.sharding.Mesh,
    axis: str,
    layer_fn: Callable,
    loss_fn: Callable,  # (outputs, targets) -> scalar (mean over items)
) -> jax.Array:
    out = gpipe_forward(
        stage_params, x, mesh=mesh, axis=axis, layer_fn=layer_fn
    )
    return loss_fn(out, targets)
