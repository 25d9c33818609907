"""Property tests for the sharding rules engine."""

import math

import jax
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import (
    DEFAULT_RULES,
    MeshContext,
    fsdp_spec,
)


def _ctx(shape=(16, 16), axes=("data", "model"), dp=("data",)):
    return MeshContext(
        mesh=AbstractMesh(shape, axes), dp_axes=dp
    )


def _axis_sizes(ctx, entry):
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    return math.prod(ctx.mesh.shape[a] for a in axes)


LOGICALS = sorted(DEFAULT_RULES)


@settings(max_examples=60, deadline=None)
@given(
    dims=st.lists(
        st.sampled_from([1, 2, 8, 12, 16, 60, 64, 128, 256, 151936]),
        min_size=1,
        max_size=5,
    ),
    logicals=st.lists(
        st.sampled_from(LOGICALS + ["nonexistent"]),
        min_size=5,
        max_size=5,
    ),
)
def test_specs_always_legal(dims, logicals):
    """Invariants for every spec the engine can emit:
    1. each sharded dim is divisible by its mesh-axes product;
    2. no mesh axis is used twice within one spec;
    3. spec arity never exceeds rank."""
    ctx = _ctx()
    shape = tuple(dims)
    axes = tuple(logicals[: len(shape)])
    spec = ctx.spec_for(shape, axes)
    assert len(spec) <= len(shape)
    used = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        size = _axis_sizes(ctx, entry)
        assert dim % size == 0, (shape, axes, spec)
        if entry is not None:
            entry_axes = entry if isinstance(entry, tuple) else (entry,)
            used.extend(entry_axes)
    assert len(used) == len(set(used)), (shape, axes, spec)


@settings(max_examples=40, deadline=None)
@given(
    dims=st.lists(
        st.sampled_from([1, 3, 8, 16, 64, 256, 640]),
        min_size=1,
        max_size=4,
    )
)
def test_fsdp_spec_legal_and_supersedes(dims):
    """FSDP specs stay legal and only ever ADD dp sharding."""
    ctx = _ctx()
    shape = tuple(dims)
    axes = ("layers",) + (None,) * (len(shape) - 1)
    base = ctx.spec_for(shape, axes)
    fsdp = fsdp_spec(ctx, shape, axes)
    # Every base entry is preserved.
    for i, entry in enumerate(tuple(base)):
        if entry is not None:
            assert tuple(fsdp)[i] == entry
    # Divisibility still holds.
    for dim, entry in zip(shape, tuple(fsdp) + (None,) * len(shape)):
        assert dim % _axis_sizes(ctx, entry) == 0


def test_known_arch_cases():
    ctx = _ctx()
    # qwen3: 32 q-heads shard, 8 kv-heads cannot (16-way axis).
    assert ctx.spec_for((2560, 32, 128), ("embed", "heads", "head_dim")) \
        == P(None, "model")
    assert ctx.spec_for((2560, 8, 128), ("embed", "kv_heads", "head_dim")) \
        == P()
    # gemma vocab 256000 shards; whisper's padded 51968 shards.
    assert ctx.spec_for((256000, 2048), ("vocab", "embed")) == P("model")
    assert ctx.spec_for((51968, 768), ("vocab", "embed")) == P("model")
    # qwen2-moe: 64 padded experts shard over model.
    assert ctx.spec_for(
        (64, 2048, 1408), ("experts", "embed", "expert_ffn")
    ) == P("model")
    # Multi-pod batch: 256 over (pod, data) = 32.
    ctx3 = _ctx((2, 16, 16), ("pod", "data", "model"), ("pod", "data"))
    assert ctx3.spec_for((256, 4096), ("batch", "seq_act")) == P(
        ("pod", "data")
    )


def test_sequence_parallel_override():
    ctx = _ctx().with_rules(seq_act=("model",))
    assert ctx.spec_for((16, 4096, 2560), ("batch", "seq_act", "embed")) \
        == P("data", "model")
