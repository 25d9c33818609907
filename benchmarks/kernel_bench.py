"""Kernel microbenchmarks: Pallas (interpret) vs jnp oracle vs model path.

CPU wall times validate FUNCTIONAL parity only -- the TPU is the target
for the Pallas path.  The derived column reports achieved GFLOP/s of the
pure-XLA blocked attention on this host as a sanity signal, plus the
analytic VMEM working set of each kernel's tiling (must be < ~16 MB).
"""

import time

import jax
import jax.numpy as jnp

from repro.analysis.constants import VMEM_BYTES


def _time(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(
        fn(*args), tuple
    ) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run() -> list[tuple[str, float, str]]:
    rows = []
    key = jax.random.PRNGKey(0)

    # Blocked attention (model XLA path).
    from repro.models.attention import blocked_attention

    b, s, h, d = 2, 1024, 8, 64
    q = jax.random.normal(key, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(key, (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(key, (b, s, h, d), jnp.bfloat16)
    fn = jax.jit(
        lambda q, k, v: blocked_attention(q, k, v, q_block=256, kv_block=256)
    )
    us = _time(fn, q, k, v)
    flops = 4 * b * h * s * s * d / 2  # causal
    rows.append(
        (
            "kernel_blocked_attention_xla",
            us,
            f"{flops / us / 1e3:.1f}GFLOP/s host",
        )
    )

    # Pallas flash attention, interpret mode (functional).
    from repro.kernels import ops

    qs = q[:, :256]
    ks, vs = k[:, :256], v[:, :256]
    fn = jax.jit(
        lambda q, k, v: ops.flash_attention(
            q, k, v, q_block=128, kv_block=128, interpret=True
        )
    )
    us = _time(fn, qs, ks, vs)
    vmem = (128 * d * 2) * 3 + 128 * d * 4 + 128 * 8
    rows.append(
        (
            "kernel_flash_attention_pallas_interpret",
            us,
            f"vmem_tile={vmem / 1e3:.0f}KB<{VMEM_BYTES / 1e6:.0f}MB",
        )
    )

    # SSD scan kernel.
    x = jax.random.normal(key, (2, 512, 4, 64))
    dt = jax.nn.softplus(jax.random.normal(key, (2, 512, 4)))
    a_log = jax.random.normal(key, (4,)) * 0.5
    bb = jax.random.normal(key, (2, 512, 64))
    cc = jax.random.normal(key, (2, 512, 64))
    fn = jax.jit(
        lambda *a: ops.ssd_scan(*a, chunk=128, interpret=True)
    )
    us = _time(fn, x, dt, a_log, bb, cc)
    vmem = 128 * 128 * 4 + 2 * 128 * 64 * 4 + 64 * 64 * 4
    rows.append(
        (
            "kernel_ssd_scan_pallas_interpret",
            us,
            f"vmem_tile={vmem / 1e3:.0f}KB",
        )
    )

    # Fused reduce (the collective local-combine).
    a = jax.random.normal(key, (1 << 20,), jnp.bfloat16)
    b2 = jax.random.normal(key, (1 << 20,), jnp.bfloat16)
    fn = jax.jit(
        lambda a, b: ops.fused_reduce(a, b, interpret=True)
    )
    us = _time(fn, a, b2)
    rows.append(
        (
            "kernel_fused_reduce_pallas_interpret",
            us,
            f"{3 * a.size * 2 / us / 1e3:.2f}GB/s host",
        )
    )

    # RMSNorm.
    x = jax.random.normal(key, (2048, 1024), jnp.bfloat16)
    w = jax.random.normal(key, (1024,))
    fn = jax.jit(lambda x, w: ops.rmsnorm(x, w, interpret=True))
    us = _time(fn, x, w)
    rows.append(("kernel_rmsnorm_pallas_interpret", us, "functional"))

    # Schedule-IR timing scan (the batched sweep recurrence).  Interpret
    # mode wall time is the interpreter's; the parity vs the numpy
    # backend is the signal (also gated in tests/test_ir_backends.py).
    import numpy as np

    from repro.core import OpticalFabric, get_pattern, strawman_instance
    from repro.core.ir import get_backend
    from repro.core.ir.engine import pack_instances
    from repro.kernels.timing_scan import timing_scan

    instances = [
        strawman_instance(
            OpticalFabric(8, 4, t_recfg=25e-6 * (1 + k)),
            get_pattern("rabenseifner_allreduce", 8, 1e6 * (1 + k)),
            prestage=True,
        )
        for k in range(32)
    ]
    packed = pack_instances(instances, None)
    from repro.core.ir import x64

    with x64():
        fn = lambda: timing_scan(packed, interpret=True)
        jax.block_until_ready(fn()[0])
        t0 = time.perf_counter()
        cct = fn()[0]
        jax.block_until_ready(cct)
        us = (time.perf_counter() - t0) * 1e6
    err = float(
        np.max(np.abs(np.asarray(cct) - get_backend("numpy")
                      .derive_timing(packed).cct))
    )
    rows.append(
        (
            "kernel_timing_scan_pallas_interpret",
            us,
            f"{len(instances)} cells max_cct_err={err:.1e}",
        )
    )
    return rows


if __name__ == "__main__":
    for name, us, note in run():
        print(f"{name},{us:.1f},{note}")
