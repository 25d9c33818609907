"""Per-architecture smoke tests (reduced same-family configs, CPU).

For each assigned architecture: one train step (finite loss, correct
shapes) and autoregressive cache consistency -- prefilling S tokens must
give the same last-position logits as prefilling S-k and decoding k steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeCell
from repro.configs.inputs import make_batch
from repro.configs.registry import ARCH_IDS, get_config, smoke_config
from repro.models.lm import build_model
from repro.sharding.rules import single_device_context

CTX = single_device_context()
TRAIN_CELL = ShapeCell("smoke_train", "train", 64, 2)
PREFILL_CELL = ShapeCell("smoke_prefill", "prefill", 48, 2)


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch(request):
    cfg = smoke_config(request.param)
    model = build_model(cfg, CTX)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_exact_assigned_config_fields():
    """The full configs carry the exact assigned hyperparameters."""
    expect = {
        "qwen3_4b": (36, 2560, 32, 8, 9728, 151936),
        "gemma_2b": (18, 2048, 8, 1, 16384, 256000),
        "qwen2_1_5b": (28, 1536, 12, 2, 8960, 151936),
        "h2o_danube3_4b": (24, 3840, 32, 8, 10240, 32000),
        "zamba2_1_2b": (38, 2048, 32, 32, 8192, 32000),
        "pixtral_12b": (40, 5120, 32, 8, 14336, 131072),
        "llama4_scout_17b_16e": (48, 5120, 40, 8, 8192, 202048),
        "whisper_small": (12, 768, 12, 12, 3072, 51865),
    }
    for name, (nl, dm, nh, nkv, dff, vocab) in expect.items():
        cfg = get_config(name)
        assert (
            cfg.n_layers,
            cfg.d_model,
            cfg.n_heads,
            cfg.n_kv_heads,
            cfg.d_ff,
            cfg.vocab_size,
        ) == (nl, dm, nh, nkv, dff, vocab), name
    mamba = get_config("mamba2_130m")
    assert (mamba.n_layers, mamba.d_model, mamba.ssm_state) == (24, 768, 128)
    moe = get_config("qwen2_moe_a2_7b")
    assert (moe.n_experts, moe.top_k, moe.moe_d_ff) == (60, 4, 1408)
    l4 = get_config("llama4_scout_17b_16e")
    assert (l4.n_experts, l4.top_k) == (16, 1)


def test_long500k_skips_match_design():
    subquadratic = {"mamba2_130m", "zamba2_1_2b", "h2o_danube3_4b"}
    for name in ARCH_IDS:
        cfg = get_config(name)
        skipped = "long_500k" in cfg.skip_shapes
        assert skipped == (name not in subquadratic), name


def test_train_step(arch):
    cfg, model, params = arch
    batch = make_batch(cfg, TRAIN_CELL, jax.random.PRNGKey(1))
    with jax.set_mesh(CTX.mesh):
        loss, metrics = jax.jit(model.loss_fn)(params, batch)
    assert np.isfinite(float(loss)), cfg.name
    assert float(loss) > 0
    assert np.isfinite(float(metrics["ce"]))


def test_grads_finite(arch):
    cfg, model, params = arch
    batch = make_batch(cfg, TRAIN_CELL, jax.random.PRNGKey(2))
    with jax.set_mesh(CTX.mesh):
        grads = jax.jit(
            jax.grad(lambda p, b: model.loss_fn(p, b)[0])
        )(params, batch)
    leaves = jax.tree.leaves(grads)
    assert leaves
    for g in leaves:
        assert np.all(np.isfinite(np.asarray(g, dtype=np.float32)))


def test_prefill_decode_consistency(arch):
    """prefill(S) last-logits == prefill(S-k) + k decode steps.

    MoE archs included: the capacity-consistent decode path (causal
    per-sequence drops + expert-count cache threading) makes batched
    prefill and per-token decode drop identical tokens.
    """
    cfg, model, params = arch
    batch = make_batch(cfg, PREFILL_CELL, jax.random.PRNGKey(3))
    tokens = batch["tokens"]
    s = tokens.shape[1]
    k = 3
    with jax.set_mesh(CTX.mesh):
        full_logits, _ = jax.jit(model.prefill)(params, batch)

        short = dict(batch)
        short["tokens"] = tokens[:, : s - k]
        _, cache = jax.jit(model.prefill)(params, short)
        # Decode caches are allocated at full length; prefill returns
        # capacity == prefilled length, so re-pad to s for decoding.
        cache = _grow_cache(model, cache, batch, s)
        logits = None
        decode = jax.jit(model.decode_step)
        for t in range(s - k, s):
            logits, cache = decode(params, cache, tokens[:, t : t + 1])
    np.testing.assert_allclose(
        np.asarray(logits, np.float32),
        np.asarray(full_logits, np.float32),
        rtol=0.05,
        atol=0.05,
    )


def _grow_cache(model, cache, batch, max_len):
    """Pad prefill-sized KV caches up to ``max_len`` capacity."""
    cfg = model.cfg
    specs = model.cache_specs(batch["tokens"].shape[0], max_len)
    grown = {}
    for name, value in cache.items():
        spec = specs[name]
        if value.ndim >= 3 and value.shape != spec.shape:
            pads = [(0, t - c) for c, t in zip(value.shape, spec.shape)]
            # Ring caches (SWA) never need growing; only plain KV does.
            if any(p[1] < 0 for p in pads):
                grown[name] = value
                continue
            grown[name] = jnp.pad(value, pads)
        else:
            grown[name] = value
    del cfg
    return grown


def test_decode_from_scratch(arch):
    """Greedy decode from empty cache produces finite logits."""
    cfg, model, params = arch
    b = 2
    max_len = 16
    from repro.models.common import init_params

    cache = init_params(
        model.cache_specs(b, max_len), jax.random.PRNGKey(0)
    )
    tok = jnp.ones((b, 1), jnp.int32)
    with jax.set_mesh(CTX.mesh):
        decode = jax.jit(model.decode_step)
        for _ in range(4):
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    assert int(cache["length"][0]) == 4
