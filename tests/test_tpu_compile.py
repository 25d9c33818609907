"""The planner's device programs compile for a TPU v5e chip.

Compiles -- without a chip -- the three programs `chip_smoke.py` runs,
at its phase-(a) shapes (1,024 cells of 128-node pairwise all-to-all,
127 steps, 8 planes, ``max_enumerated_planes=4``), the CHAIN scan
at a padded 128-cell bucket of 4 planes (the small sweep's shape), and
the Topology-Bypassing programs of a relay sweep (1,024 cells of
512-rank Bruck all-to-all, 9 steps, 4 planes, ``bypass_depth=2``: the
twin scan and the timing scan with relay rows), for one chip of a
described ``v5e:2x2`` topology.  This is the only file that describes
the chip: the topology is built inside a fixture, never at import, so
every test worker collects the same tests and only the worker running
this file loads the TPU compiler.

Also pins how the Pallas backend picks interpret mode, and where the
persistent compilation cache goes.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import SingleDeviceSharding  # noqa: E402

import repro  # noqa: E402
from repro.core import (  # noqa: E402
    OpticalFabric,
    bruck_alltoall,
    pairwise_alltoall,
)
from repro.core import greedy as _greedy  # noqa: E402
from repro.core.baselines import strawman_instance  # noqa: E402
from repro.core.ir import fused, x64  # noqa: E402
from repro.core.ir.backends import (  # noqa: E402
    PallasBackend,
    _build_jax_timing,
    _bucket,
    pad_packed,
    relay_buckets,
)
from repro.core.ir.engine import pack_instances  # noqa: E402
from repro.core.schedule import DependencyMode  # noqa: E402

_NODES, _PLANES, _SIDE = 128, 8, 32
_ENUM_PLANES, _HORIZON = 4, 24


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _grid_cells():
    patterns = [
        pairwise_alltoall(_NODES, 1e6 * (1 + i)) for i in range(_SIDE)
    ]
    return [
        (OpticalFabric(_NODES, _PLANES, t_recfg=12.5e-6 * (1 + j)), p)
        for p in patterns
        for j in range(_SIDE)
    ]


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _timing_args(padded):
    return [
        padded[k]
        for k in (
            "vol", "step_vol", "step_cfg", "step_mask", "plane_mask", "bw",
            "init", "t_recfg", "chain", "ready", "byp_vol", "byp_plane",
        )
    ]


@pytest.mark.parametrize("attribution", [False, True])
def test_jax_timing_scan_compiles(one_chip, attribution):
    inst = strawman_instance(
        OpticalFabric(_NODES, _PLANES), pairwise_alltoall(_NODES, 1e6)
    )
    packed = pack_instances([inst], None)
    _, s, p = packed["vol"].shape
    padded = pad_packed(packed, _bucket(_SIDE * _SIDE), s, _bucket(p))
    assert padded["vol"].shape == (1024, 127, 8)
    args = _timing_args(padded)
    with x64():
        compiled = (
            _build_jax_timing(attribution)
            .lower(*_shapes(args, one_chip))
            .compile()
        )
    assert compiled.memory_analysis() is not None


def test_fused_chain_scan_compiles(one_chip):
    st = _greedy._GridState(
        _grid_cells(),
        mode=DependencyMode.CHAIN,
        max_enumerated_planes=_ENUM_PLANES,
    )
    with x64():
        tab = fused._chain_tables(st, with_bypass=False)
        assert tab["step_vol"].dtype == np.float64
        compiled = (
            fused._chain_scan(_HORIZON, False)
            .lower(_shapes(tab, one_chip))
            .compile()
        )
    assert compiled.memory_analysis() is not None


def test_bucketed_chain_scan_compiles(one_chip):
    # 100 cells of the a2a128_4p sweeps' shape (4 planes, every reserve
    # subset enumerated), padded to the 128-cell bucket.
    pattern = pairwise_alltoall(_NODES, 1e6)
    cells = [
        (OpticalFabric(_NODES, 4, t_recfg=12.5e-6 * (1 + i)), pattern)
        for i in range(100)
    ]
    st = _greedy._GridState(
        cells, mode=DependencyMode.CHAIN, max_enumerated_planes=4
    )
    with x64():
        tab = fused._chain_tables(st, False, 128)
        assert tab["n_s"].shape == (128,)
        assert tab["cand_inst"].shape == (128 * 15,)
        compiled = (
            fused._chain_scan(_HORIZON, False)
            .lower(_shapes(tab, one_chip))
            .compile()
        )
    assert compiled.memory_analysis() is not None


_RELAY_NODES, _RELAY_PLANES = 512, 4


@pytest.mark.parametrize("attribution", [False, True])
def test_jax_timing_scan_with_relay_rows_compiles(one_chip, attribution):
    inst = strawman_instance(
        OpticalFabric(_RELAY_NODES, _RELAY_PLANES),
        bruck_alltoall(_RELAY_NODES, 1e6),
    )
    packed = pack_instances([inst], None)
    _, s, p = packed["vol"].shape
    r_pad, h_pad = relay_buckets(3, 2, _bucket(p))
    padded = pad_packed(
        packed, _bucket(_SIDE * _SIDE), s, _bucket(p), r_pad, h_pad
    )
    assert padded["byp_plane"].shape == (1024, 9, 4, 2)
    with x64():
        compiled = (
            _build_jax_timing(attribution)
            .lower(*_shapes(_timing_args(padded), one_chip))
            .compile()
        )
    assert compiled.memory_analysis() is not None


def test_relay_twin_scan_compiles(one_chip):
    pattern = bruck_alltoall(_RELAY_NODES, 1e6)
    cells = [
        (
            OpticalFabric(
                _RELAY_NODES, _RELAY_PLANES, t_recfg=8e-4 * (1 + i / 512)
            ),
            pattern,
        )
        for i in range(_SIDE * _SIDE)
    ]
    st = _greedy._GridState(
        cells, mode=DependencyMode.CHAIN,
        max_enumerated_planes=_ENUM_PLANES, bypass_depth=2,
    )
    assert st.depth_tab.any()
    with x64():
        tab = fused._chain_tables(st, with_bypass=True)
        assert tab["cand_inst"].shape == (1024 * 15,)
        compiled = (
            fused._chain_scan(_HORIZON, True)
            .lower(_shapes(tab, one_chip))
            .compile()
        )
    assert compiled.memory_analysis() is not None


def test_fused_independent_scan_compiles(one_chip):
    st = _greedy._GridState(
        _grid_cells(),
        mode=DependencyMode.INDEPENDENT,
        max_enumerated_planes=_ENUM_PLANES,
    )
    with x64():
        tab = fused._base_tables(st)
        compiled = (
            fused._independent_scan(split_mode=False)
            .lower(_shapes(tab, one_chip))
            .compile()
        )
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("platform", ["tpu", "cpu", "gpu"])
def test_pallas_interprets_only_off_the_tpu(monkeypatch, platform):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert PallasBackend().interpret is (platform != "tpu")
    # An explicit choice still wins (tests force either mode).
    assert PallasBackend(interpret=True).interpret is True
    assert PallasBackend(interpret=False).interpret is False


@pytest.fixture()
def _restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env(monkeypatch, _restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert repro.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_into_checkout(
    monkeypatch, _restore_cache_dir
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = repro.use_compile_cache()
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    )
    assert path == os.path.join(checkout, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert repro.use_compile_cache() == path  # fixed, not per run
