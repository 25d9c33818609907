"""Mixture-of-Experts with expert parallelism via shard_map + all_to_all.

Capacity-based dropped-token dispatch (Switch/GShard style), laid out for
TPU expert parallelism:

1. per-device router: top-k experts per token, gates renormalized;
2. tokens packed into a capacity buffer (E, C, D) by scatter-add;
3. ``lax.all_to_all`` over the EP mesh axis exchanges the buffer so each
   device holds the tokens destined for its local experts -- this is
   exactly the Pairwise/Bruck-schedulable all-to-all that the SWOT
   planner (`repro.core.planner`) feeds to the optical scheduler;
4. local expert FFNs (optionally FSDP: expert weights sharded over the
   data axis and all-gathered per layer);
5. the inverse all_to_all returns expert outputs, combined with gates.

Expert count is padded up to a multiple of the EP axis size (padded
experts are masked out of routing); the padding overhead is reported by
``padded_experts``.

**Capacity consistency.**  The drop rule is *causal and per-sequence*: a
token at absolute position ``p`` keeps its expert assignment iff the
number of prior assignments to that expert within its own sequence
(positions ``< p``, plus earlier top-k slots of the same token, plus the
``expert_counts`` carried in from earlier chunks) is below the
position-dependent capacity ``max(8, ceil((p+1) * top_k *
capacity_factor / n_experts))``.  Because the rule never looks at other
sequences or at future positions, batched prefill and per-token decode
drop the *same* tokens -- thread ``base_pos`` (absolute position of each
sequence's first token) and ``expert_counts`` (per-sequence running
assignment totals, returned with ``return_counts=True``) through decode
and the two paths agree exactly.  Token-sliced / sequence-sharded EP
dispatch approximates the rule shard-locally (slices restart the causal
count), so capacity-consistent decode requires the plain dispatch path.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ParamSpec, activation


@dataclasses.dataclass(frozen=True)
class MoeDims:
    n_experts: int  # real experts
    n_experts_padded: int  # padded to a multiple of the EP axis size
    top_k: int
    d_model: int
    d_ff: int
    capacity_factor: float

    @classmethod
    def for_mesh(
        cls,
        n_experts: int,
        top_k: int,
        d_model: int,
        d_ff: int,
        ep_size: int,
        capacity_factor: float = 1.25,
    ) -> "MoeDims":
        padded = math.ceil(n_experts / ep_size) * ep_size
        return cls(
            n_experts=n_experts,
            n_experts_padded=padded,
            top_k=top_k,
            d_model=d_model,
            d_ff=d_ff,
            capacity_factor=capacity_factor,
        )


def moe_param_specs(dims: MoeDims, fsdp_experts: bool) -> dict[str, Any]:
    e, d, f = dims.n_experts_padded, dims.d_model, dims.d_ff
    ffn_axis = "expert_ffn_fsdp" if fsdp_experts else "expert_ffn"
    return {
        "router": ParamSpec((d, e), ("embed", "experts_router")),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed", ffn_axis)),
        "w_up": ParamSpec((e, d, f), ("experts", "embed", ffn_axis)),
        "w_down": ParamSpec((e, f, d), ("experts", ffn_axis, "embed")),
    }


def _dispatch_indices(
    logits: jax.Array,  # (T, E) fp32, padded experts already masked
    top_k: int,
    n_seqs: int,
    base_pos: jax.Array,  # (n_seqs,) int32 absolute first positions
    prior_counts: jax.Array,  # (n_seqs, E) int32 carried-in assignments
    capacity_factor: float,
    n_experts: int,
):
    """Causal per-sequence top-k routing with positional capacity.

    Rows are ``n_seqs`` contiguous sequences of ``T / n_seqs`` tokens.  A
    token's assignment ranks against prior same-sequence assignments only
    (earlier positions + earlier slots of the same token + carried-in
    ``prior_counts``), and keeps iff the rank is below the
    position-dependent capacity -- the batch-shape-invariant rule that
    makes prefill and decode drop identically.  Buffer positions are
    ranks among *kept* assignments over the whole call, so distinct kept
    tokens land in distinct (expert, slot) cells.

    Returns ``(expert_ids, gates, buffer_pos, keep)`` each shaped
    ``(T*k,)`` plus the updated ``(n_seqs, E)`` assignment counts.
    """
    t, e = logits.shape
    s_loc = t // n_seqs
    top_logits, top_idx = jax.lax.top_k(logits, top_k)  # (T, k)
    gates = jax.nn.softmax(top_logits, axis=-1)
    e_flat = top_idx.reshape(-1)
    g_flat = gates.reshape(-1)
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)  # (T*k, E)
    per_seq = onehot.reshape(n_seqs, s_loc * top_k, e)
    prior = jnp.cumsum(per_seq, axis=1) - per_seq
    prior = prior + prior_counts[:, None, :]
    rank = jnp.take_along_axis(
        prior.reshape(t * top_k, e), e_flat[:, None], axis=1
    )[:, 0]
    pos = base_pos[:, None] + jnp.arange(s_loc, dtype=jnp.int32)
    cap = jnp.maximum(
        8,
        jnp.ceil(
            (pos + 1).astype(jnp.float32)
            * top_k
            * capacity_factor
            / n_experts
        ).astype(jnp.int32),
    )
    keep = rank < jnp.repeat(cap.reshape(-1), top_k)
    kept = onehot * keep[:, None].astype(jnp.int32)
    buf_rank = jnp.cumsum(kept, axis=0) - kept
    buf_pos = jnp.take_along_axis(
        buf_rank, e_flat[:, None], axis=1
    )[:, 0]
    new_counts = prior_counts + per_seq.sum(axis=1)
    return e_flat, g_flat, buf_pos, keep, new_counts


def _local_moe(
    x: jax.Array,  # (T, D) local tokens, compute dtype
    router: jax.Array,  # (D, E)
    w_gate: jax.Array,  # (E_loc, D, F) local experts
    w_up: jax.Array,
    w_down: jax.Array,  # (E_loc, F, D)
    dims: MoeDims,
    act_name: str,
    ep_axis: str | None,
    fsdp_axis: str | None,
    n_seqs: int,
    base_pos: jax.Array,  # (n_seqs,) int32
    prior_counts: jax.Array,  # (n_seqs, E) int32
    zero_base: bool,
):
    """Per-device MoE body (runs inside shard_map).

    ``zero_base`` (static) asserts every sequence starts at position 0
    with no carried-in counts, which lets the dispatch buffer use the
    tighter end-of-call capacity bound instead of the all-kept worst
    case.
    """
    t, d = x.shape
    e = dims.n_experts_padded
    act = activation(act_name)
    # Static per-expert buffer bound on *kept* assignments: per sequence
    # at most s_loc * k slots, and with zero-base positions at most the
    # end-of-call positional capacity.
    s_loc = t // n_seqs
    per_seq = s_loc * dims.top_k
    if zero_base:
        per_seq = min(
            per_seq,
            max(8, math.ceil(per_seq * dims.capacity_factor / e)),
        )
    capacity = max(1, n_seqs * per_seq)

    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))
    if dims.n_experts != e:
        pad_mask = jnp.arange(e) < dims.n_experts
        logits = jnp.where(pad_mask[None], logits, -1e30)
    # The positional-capacity denominator is the padded expert count --
    # the same normalization as the buffer bound above, so kept
    # assignments can never overflow the (E, C, D) scatter buffer.
    e_flat, g_flat, pos, keep, new_counts = _dispatch_indices(
        logits, dims.top_k, n_seqs, base_pos, prior_counts,
        dims.capacity_factor, e,
    )
    t_flat = jnp.repeat(jnp.arange(t), dims.top_k)

    # Load-balance auxiliary loss (Switch-style) and drop statistics.
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    token_frac = (
        jax.ops.segment_sum(
            jnp.where(keep, 1.0, 0.0), e_flat, num_segments=e
        )
        / jnp.maximum(t * dims.top_k, 1)
    )
    aux_loss = dims.n_experts * jnp.sum(token_frac * jnp.mean(probs, axis=0))
    drop_frac = 1.0 - jnp.mean(jnp.where(keep, 1.0, 0.0))

    # Scatter tokens into the capacity buffer (E, C, D).
    buf = jnp.zeros((e, capacity, d), x.dtype)
    upd = jnp.where(keep[:, None], x[t_flat], 0).astype(x.dtype)
    buf = buf.at[e_flat, pos].add(upd, mode="drop")

    if ep_axis is not None:
        # (E, C, D) -> (E_loc, ep*C, D): every device receives the slices
        # destined for its local experts from all EP peers.
        buf = jax.lax.all_to_all(
            buf, ep_axis, split_axis=0, concat_axis=1, tiled=True
        )

    # Expert matmuls run in the activations' compute dtype (bf16); cast
    # BEFORE the FSDP gather so the per-layer weight collective moves
    # half the bytes of the stored fp32 master weights.
    w_gate = w_gate.astype(x.dtype)
    w_up = w_up.astype(x.dtype)
    w_down = w_down.astype(x.dtype)
    if fsdp_axis is not None:
        w_gate = jax.lax.all_gather(
            w_gate, fsdp_axis, axis=2, tiled=True
        )
        w_up = jax.lax.all_gather(w_up, fsdp_axis, axis=2, tiled=True)
        w_down = jax.lax.all_gather(w_down, fsdp_axis, axis=1, tiled=True)

    h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up
    )
    out = jnp.einsum("ecf,efd->ecd", h, w_down)

    if ep_axis is not None:
        out = jax.lax.all_to_all(
            out, ep_axis, split_axis=1, concat_axis=0, tiled=True
        )

    # Combine expert outputs back to token order, weighted by gates.
    gathered = out[e_flat, pos]  # (T*k, D)
    weights = jnp.where(keep, g_flat, 0.0).astype(out.dtype)
    y = jax.ops.segment_sum(
        gathered * weights[:, None], t_flat, num_segments=t
    )
    return y.astype(x.dtype), aux_loss, drop_frac, new_counts


def moe_ffn(
    x: jax.Array,  # (B, S, D) global view
    params: dict[str, jax.Array],
    dims: MoeDims,
    *,
    mesh: jax.sharding.Mesh,
    dp_axes: tuple[str, ...],
    ep_axis: str,
    act_name: str = "silu",
    fsdp_experts: bool = False,
    token_slice: bool = False,
    seq_sharded: bool = False,
    base_pos: jax.Array | None = None,
    expert_counts: jax.Array | None = None,
    return_counts: bool = False,
):
    """Expert-parallel MoE FFN: returns (y, aux_loss, drop_frac).

    ``token_slice`` (beyond-baseline Perf lever): activations are
    replicated over the EP/model axis, so by default every EP rank
    redundantly routes and dispatches the full dp-local token set (~ep x
    wasted dispatch FLOPs and ep x oversized all_to_all buffers).  With
    slicing, each EP rank dispatches only its 1/ep slice of the tokens
    and the combined outputs are re-assembled with one all_gather.

    ``seq_sharded`` (sequence-parallel fusion): consume the residual
    stream already sharded over the EP axis on the sequence dim -- the
    SP shard IS the token slice, so neither the input all-gather nor the
    output re-assembly collective is needed at all.

    Capacity-consistent decode (the causal drop rule, module docstring):
    ``base_pos`` (B,) gives each sequence's absolute first position
    (``None`` = 0) and ``expert_counts`` (B, E_padded) the per-sequence
    assignment totals carried in from earlier chunks; with
    ``return_counts=True`` a fourth output returns the updated counts to
    thread through a decode cache.  The counts contract holds on the
    plain dispatch path; sliced/sequence-sharded dispatch returns the
    input counts unchanged (shard-local causal approximation).
    """
    b, s, d = x.shape
    e_pad = dims.n_experts_padded
    ep_size = mesh.shape[ep_axis]
    ep = ep_axis if ep_size > 1 else None
    seq_sharded = seq_sharded and ep is not None and s % ep_size == 0
    zero_base = base_pos is None and expert_counts is None
    bpos = (
        jnp.zeros((b,), jnp.int32)
        if base_pos is None
        else base_pos.astype(jnp.int32)
    )
    counts_in = (
        jnp.zeros((b, e_pad), jnp.int32)
        if expert_counts is None
        else expert_counts.astype(jnp.int32)
    )
    fsdp_axis = None
    expert_ffn_spec: str | None = None
    if fsdp_experts:
        # Expert FFN dim sharded over the (flattened) dp axes.
        fsdp_axis = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        expert_ffn_spec = fsdp_axis

    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    x_spec = P(dp_spec, ep_axis if seq_sharded else None, None)
    expert_spec = P(ep_axis if ep_size > 1 else None, None, expert_ffn_spec)
    down_spec = P(ep_axis if ep_size > 1 else None, expert_ffn_spec, None)
    seq_state_spec = P(dp_spec)
    counts_spec = P(dp_spec, None)

    def body(xb, router, w_gate, w_up, w_down, bp, counts):
        xt = xb.reshape(-1, d)
        t_full = xt.shape[0]
        sliced = (
            not seq_sharded
            and token_slice
            and ep is not None
            and t_full % ep_size == 0
        )
        if sliced:
            rank = jax.lax.axis_index(ep_axis)
            t_loc = t_full // ep_size
            xt = jax.lax.dynamic_slice_in_dim(xt, rank * t_loc, t_loc)
        if seq_sharded:
            # Per-rank sequence shard: positions offset by the shard
            # start; the causal rule applies within the shard only.
            n_seqs = xb.shape[0]
            bp_loc = bp + jax.lax.axis_index(ep_axis) * xb.shape[1]
            counts_loc = counts
            zb = False
        elif sliced:
            # Flat token slice: one anonymous zero-based sequence block.
            n_seqs = 1
            bp_loc = jnp.zeros((1,), jnp.int32)
            counts_loc = jnp.zeros((1, e_pad), jnp.int32)
            zb = True
        else:
            n_seqs = xb.shape[0]
            bp_loc = bp
            counts_loc = counts
            zb = zero_base
        y, aux, drop, new_counts = _local_moe(
            xt,
            router,
            w_gate,
            w_up,
            w_down,
            dims,
            act_name,
            ep,
            fsdp_axis if fsdp_experts else None,
            n_seqs,
            bp_loc,
            counts_loc,
            zb,
        )
        if sliced:
            # Rank-ordered slices reassemble with one all_gather.
            y = jax.lax.all_gather(y, ep_axis, axis=0, tiled=True)
        if sliced or seq_sharded:
            # Shard-local counts are partial; the consistency contract is
            # documented for the plain path only.
            new_counts = counts
        # Average the scalar diagnostics over the data axes (plus the EP
        # axis when token slices differ per rank).
        stat_axes = dp_axes + (
            (ep_axis,) if (sliced or seq_sharded) else ()
        )
        aux = jax.lax.pmean(aux, stat_axes)
        drop = jax.lax.pmean(drop, stat_axes)
        return y.reshape(xb.shape), aux, drop, new_counts

    # check_vma=False: every device in a data row holds identical tokens
    # (x replicated over the model axis), so y/aux/drop are replicated over
    # 'model' by construction -- but the static varying-axes checker cannot
    # see through all_to_all.  The redundant per-row dispatch compute this
    # implies is a recorded Perf lever (EP token slicing, EXPERIMENTS.md).
    y, aux, drop, counts_out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            x_spec, P(), expert_spec, expert_spec, down_spec,
            seq_state_spec, counts_spec,
        ),
        out_specs=(x_spec, P(), P(), counts_spec),
        check_vma=False,
    )(
        x, params["router"], params["w_gate"], params["w_up"],
        params["w_down"], bpos, counts_in,
    )
    if return_counts:
        return y, aux, drop, counts_out
    return y, aux, drop


def moe_reference(
    x: jax.Array,  # (T, D)
    params: dict[str, jax.Array],
    dims: MoeDims,
    act_name: str = "silu",
) -> jax.Array:
    """Dense single-device oracle: loops experts, no capacity drops."""
    act = activation(act_name)
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    if dims.n_experts != dims.n_experts_padded:
        mask = jnp.arange(dims.n_experts_padded) < dims.n_experts
        logits = jnp.where(mask[None], logits, -1e30)
    top_logits, top_idx = jax.lax.top_k(logits, dims.top_k)
    gates = jax.nn.softmax(top_logits, axis=-1)
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for e in range(dims.n_experts):
        h = act(x @ params["w_gate"][e]) * (x @ params["w_up"][e])
        out = (h @ params["w_down"][e]).astype(jnp.float32)
        weight = jnp.sum(
            jnp.where(top_idx == e, gates, 0.0), axis=-1
        )  # (T,)
        y += out * weight[:, None]
    return y.astype(x.dtype)
