"""Attention implementations: blocked (flash-style, pure XLA) and decode.

``blocked_attention`` is the training/prefill path: online-softmax over
key/value blocks with O(S * block) memory instead of the O(S^2) logits
tensor (which would not fit HBM at the 32k prefill shapes).  Two modes:

* default: ``lax.map`` over query blocks (one compiled body -> small HLO,
  scan trip counts handled by the roofline HLO walker); every KV block is
  computed and masked, so causal attention does ~2x the minimal FLOPs;
* ``skip_blocks=True``: python-unrolled query blocks with trace-time
  skipping of fully-masked KV blocks -- the minimal-FLOPs variant (larger
  HLO; used as a Perf-iteration lever, see EXPERIMENTS.md section Perf).

``decode_attention`` scores a single query against a KV cache.  The
sharded long-context variant (cache sharded over the data axis, partial
softmax merged via LSE) lives in `repro.serve.engine`.

The Pallas TPU kernel equivalent is `repro.kernels.flash_attention`; model
configs choose the implementation via ``attention_impl``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


_NEG_INF = -1e30


def _block_scores(
    q_blk: jax.Array,  # (B, qb, Hq, D)
    k_blk: jax.Array,  # (B, kb, Hkv, D)
    scale: float,
) -> jax.Array:
    """Grouped-query scores (B, Hq, qb, kb) in fp32."""
    b, qb, hq, d = q_blk.shape
    _, kb, hkv, _ = k_blk.shape
    group = hq // hkv
    q32 = q_blk.astype(jnp.float32).reshape(b, qb, hkv, group, d)
    k32 = k_blk.astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q32, k32) * scale
    return scores.reshape(b, hq, qb, kb)


def _apply_mask(
    scores: jax.Array,  # (B, Hq, qb, kb)
    q_pos: jax.Array,  # (qb,)
    kv_pos: jax.Array,  # (kb,)
    kv_len: int,
    causal: bool,
    window: int | None,
) -> jax.Array:
    mask = kv_pos[None, :] < kv_len  # padding
    if causal:
        mask = mask & (kv_pos[None, :] <= q_pos[:, None])
    if window is not None:
        mask = mask & (q_pos[:, None] - kv_pos[None, :] < window)
    return jnp.where(mask[None, None], scores, _NEG_INF)


def _attend_block(
    carry: tuple[jax.Array, jax.Array, jax.Array],
    q_blk: jax.Array,
    k_blk: jax.Array,
    v_blk: jax.Array,
    q_pos: jax.Array,
    kv_pos: jax.Array,
    kv_len: int,
    causal: bool,
    window: int | None,
    scale: float,
    probs_bf16: bool = False,
):
    """One online-softmax accumulation step.

    ``probs_bf16``: cast the probability block to bf16 for the PV matmul
    (the MXU takes bf16 inputs anyway on TPU; halves the score-tensor
    traffic at ~1e-3 relative output error -- a Perf lever).
    """
    m_prev, l_prev, acc_prev = carry
    scores = _apply_mask(
        _block_scores(q_blk, k_blk, scale),
        q_pos,
        kv_pos,
        kv_len,
        causal,
        window,
    )
    m_blk = jnp.max(scores, axis=-1)  # (B, Hq, qb)
    m_new = jnp.maximum(m_prev, m_blk)
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[..., None])  # (B, Hq, qb, kb)
    b, kb, hkv, d = v_blk.shape
    hq = p.shape[1]
    group = hq // hkv
    p_mm = p.astype(jnp.bfloat16) if probs_bf16 else p
    v_mm = v_blk.astype(jnp.bfloat16 if probs_bf16 else jnp.float32)
    pv = jnp.einsum(
        "bhgqk,bkhd->bhgqd",
        p_mm.reshape(b, hkv, group, p.shape[2], kb),
        v_mm,
        preferred_element_type=jnp.float32,
    ).reshape(b, hq, p.shape[2], d)
    l_new = l_prev * correction + jnp.sum(p, axis=-1)
    acc_new = acc_prev * correction[..., None] + pv
    return m_new, l_new, acc_new


def blocked_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,  # (B, Skv, Hkv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    q_block: int = 512,
    kv_block: int = 512,
    skip_blocks: bool = False,
    probs_bf16: bool = False,
) -> jax.Array:
    """Flash-style blocked attention; returns (B, Sq, Hq, D) in q.dtype.

    ``q_offset`` is the absolute position of q[0] (prefill continuation).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    scale = 1.0 / math.sqrt(d)
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    nq = math.ceil(sq / q_block)
    nkv = math.ceil(skv / kv_block)
    sq_pad, skv_pad = nq * q_block, nkv * kv_block
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0), (0, 0)))
    if skv_pad != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))

    kv_pos_all = jnp.arange(skv_pad)

    @jax.checkpoint
    def q_block_body(args):
        q_blk, q_pos = args  # (B, qb, Hq, D), (qb,)
        m = jnp.full((b, hq, q_block), _NEG_INF, jnp.float32)
        l = jnp.zeros((b, hq, q_block), jnp.float32)
        acc = jnp.zeros((b, hq, q_block, d), jnp.float32)
        carry = (m, l, acc)
        for kb_idx in range(nkv):
            sl = slice(kb_idx * kv_block, (kb_idx + 1) * kv_block)
            carry = _attend_block(
                carry,
                q_blk,
                k[:, sl],
                v[:, sl],
                q_pos,
                kv_pos_all[sl],
                skv,
                causal,
                window,
                scale,
                probs_bf16,
            )
        m, l, acc = carry
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B, Hq, qb, D)

    if skip_blocks:
        # Trace-time causal/window block skipping (minimal FLOPs, unrolled).
        # Full k/v enter each checkpointed block (slicing happens inside),
        # so the saved residuals alias ONE buffer instead of duplicating
        # per-block KV slices.
        outs = []

        def make_q_block(qb_idx: int, kv_indices: tuple[int, ...]):
            lo = q_offset + qb_idx * q_block

            @jax.checkpoint
            def one_q_block(q_blk, k_all, v_all):
                m = jnp.full((b, hq, q_block), _NEG_INF, jnp.float32)
                l = jnp.zeros((b, hq, q_block), jnp.float32)
                acc = jnp.zeros((b, hq, q_block, d), jnp.float32)
                carry = (m, l, acc)
                q_pos = lo + jnp.arange(q_block)
                for kb_idx in kv_indices:
                    sl = slice(kb_idx * kv_block, (kb_idx + 1) * kv_block)
                    carry = _attend_block(
                        carry, q_blk, k_all[:, sl], v_all[:, sl],
                        q_pos, kv_pos_all[sl],
                        skv, causal, window, scale, probs_bf16,
                    )
                m, l, acc = carry
                return acc / jnp.maximum(l, 1e-30)[..., None]

            return one_q_block

        for qb_idx in range(nq):
            lo_pos = q_offset + qb_idx * q_block
            hi_pos = q_offset + (qb_idx + 1) * q_block - 1
            kv_indices = []
            for kb_idx in range(nkv):
                kv_lo = kb_idx * kv_block
                kv_hi = (kb_idx + 1) * kv_block - 1
                if causal and kv_lo > hi_pos:
                    continue  # entirely in the future
                if window is not None and lo_pos - kv_hi >= window:
                    continue  # entirely outside the sliding window
                if kv_lo >= skv:
                    continue  # entirely padding
                kv_indices.append(kb_idx)
            q_blk = q[:, qb_idx * q_block : (qb_idx + 1) * q_block]
            outs.append(
                make_q_block(qb_idx, tuple(kv_indices))(q_blk, k, v)
            )
        out = jnp.concatenate(outs, axis=2)  # (B, Hq, Sq_pad, D)
    else:
        q_blocks = q.reshape(b, nq, q_block, hq, d).transpose(1, 0, 2, 3, 4)
        q_positions = q_offset + jnp.arange(sq_pad).reshape(nq, q_block)
        out = jax.lax.map(q_block_body, (q_blocks, q_positions))
        # (nq, B, Hq, qb, D) -> (B, Hq, Sq_pad, D)
        out = out.transpose(1, 2, 0, 3, 4).reshape(b, hq, sq_pad, d)

    out = out[:, :, :sq].transpose(0, 2, 1, 3)  # (B, Sq, Hq, D)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, 1, Hq, D)
    k_cache: jax.Array,  # (B, Smax, Hkv, D)
    v_cache: jax.Array,  # (B, Smax, Hkv, D)
    cache_len: jax.Array,  # (B,) valid entries per sequence
    *,
    window: int | None = None,
) -> jax.Array:
    """Single-token attention against a KV cache: (B, 1, Hq, D)."""
    b, _, hq, d = q.shape
    _, smax, hkv, _ = k_cache.shape
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    q32 = q.astype(jnp.float32).reshape(b, hkv, group, d)
    scores = (
        jnp.einsum("bhgd,bshd->bhgs", q32, k_cache.astype(jnp.float32))
        * scale
    )  # (B, Hkv, G, Smax)
    pos = jnp.arange(smax)[None]  # (1, Smax)
    mask = pos < cache_len[:, None]
    if window is not None:
        mask = mask & (pos >= cache_len[:, None] - window)
    scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", probs, v_cache.astype(jnp.float32)
    ).reshape(b, 1, hq, d)
    return out.astype(q.dtype)


def sharded_decode_attention(
    q: jax.Array,  # (B, 1, Hq, D)
    k_cache: jax.Array,  # (B, Smax, Hkv, D) -- seq dim sharded over axis
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,)
    *,
    mesh: jax.sharding.Mesh,
    axis: str,
) -> jax.Array:
    """Flash-decoding for long-context caches sharded on the seq dim.

    Each shard computes a partial softmax over its cache slice; partials
    merge with the log-sum-exp trick via three tiny psums (max,
    denominator, weighted values) -- the explicit form of what GSPMD
    derives implicitly for the 500k cells, exposed for the serving
    engine's long-context path.
    """
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    smax = k_cache.shape[1]
    assert smax % n_shards == 0
    s_loc = smax // n_shards

    def body(q_blk, k_blk, v_blk, lens):
        b, _, hq, d = q_blk.shape
        hkv = k_blk.shape[2]
        group = hq // hkv
        shard = jax.lax.axis_index(axis)
        offset = shard * s_loc
        scale = 1.0 / math.sqrt(d)
        q32 = q_blk.astype(jnp.float32).reshape(b, hkv, group, d)
        scores = (
            jnp.einsum(
                "bhgd,bshd->bhgs", q32, k_blk.astype(jnp.float32)
            )
            * scale
        )  # (B, Hkv, G, s_loc)
        pos = offset + jnp.arange(s_loc)[None]
        mask = pos < lens[:, None]
        scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
        m_loc = jnp.max(scores, axis=-1)  # (B, Hkv, G)
        m_glob = jax.lax.pmax(m_loc, axis)
        p = jnp.exp(scores - m_glob[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum(
            "bhgs,bshd->bhgd", p, v_blk.astype(jnp.float32)
        )
        l_glob = jax.lax.psum(l_loc, axis)
        o_glob = jax.lax.psum(o_loc, axis)
        out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.reshape(b, 1, hq, d).astype(q_blk.dtype)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(None, axis), P(None, axis), P()),
        out_specs=P(),
        check_vma=False,  # psum-merged result is replicated
    )(q, k_cache, v_cache, cache_len)


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """O(S^2)-memory oracle used by tests."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    q32 = q.astype(jnp.float32).reshape(b, sq, hkv, group, d)
    scores = (
        jnp.einsum("bqhgd,bkhd->bhgqk", q32, k.astype(jnp.float32)) * scale
    )
    q_pos = q_offset + jnp.arange(sq)
    kv_pos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= q_pos[:, None] - kv_pos[None, :] < window
    scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)
