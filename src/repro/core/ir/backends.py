"""Pluggable timing backends for the batched schedule-IR sweep engine.

``batch_evaluate`` packs a batch of (fabric, pattern, decisions) cells
into flat padded arrays (`repro.core.ir.engine.pack_instances`); a
*timing backend* consumes that packed dict and runs the per-step timing
recurrence -- the max-plus update

    start   = max(step barrier, plane free)        (CHAIN mode)
    end     = start + volume / bandwidth
    barrier = max over active planes of end

with lazy per-plane reconfiguration -- across the whole batch.  Three
implementations share one parity contract (CCTs equal to the object-path
oracle within `repro.core.tolerances`):

* ``numpy``  -- the reference: one Python loop turn per step, vectorized
  over (batch, planes).  Deterministic, dependency-free, the default.
* ``jax``    -- the same recurrence as a ``jax.lax.scan`` over steps,
  ``jit``-compiled over the padded batch.  Inputs are padded to
  power-of-two *buckets* (batch, steps, planes) so the number of
  distinct compiled programs stays O(log^3) of the largest sweep, not
  one per sweep shape.  Runs in float64 under the scoped `x64` helper.
* ``pallas`` -- the recurrence lowered as a *blocked scan* kernel
  (`repro.kernels.timing_scan`): the grid blocks the batch dimension,
  each program carries the (block, planes) plane state through a
  ``fori_loop`` over steps.  It runs in interpret mode off the TPU (the
  tier-1 suite exercises it) and is compiled on the TPU.

Select a backend per call (``batch_evaluate(..., backend="jax")``) or
process-wide with the ``REPRO_IR_BACKEND`` env var; unset means numpy so
results stay deterministic unless an accelerator path is asked for.
"""

from __future__ import annotations

import sys
from typing import Callable

import numpy as np

from repro.core import knobs
from repro.core.ir.engine import (
    BatchResult,
    finalize_result,
)
from repro.core.knobs import (  # noqa: F401  (compat re-exports)
    ENV_IR_BACKEND as ENV_BACKEND,
)
from repro.core.tolerances import EPS_VOLUME, REL_TOL, TOL


class BackendUnavailable(RuntimeError):
    """The requested backend's dependencies are missing on this host."""


class TimingBackend:
    """One implementation of the batched per-step timing recurrence.

    ``attribution=True`` asks for the per-(instance, step, plane) CCT
    component arrays (`repro.obs.attribution`) alongside the scalar
    outputs; backends that compute them hand the raw arrays to the shared
    ``finalize_result`` epilogue, which closes the decomposition with the
    idle term so conservation is bitwise everywhere.
    """

    name: str = "abstract"

    def derive_timing(
        self, packed: dict[str, np.ndarray], attribution: bool = False
    ) -> BatchResult:
        raise NotImplementedError


def _bucket(n: int) -> int:
    """Next power of two >= n (static-shape bucketing for jit caches)."""
    return 1 << max(0, int(n - 1).bit_length())


def relay_buckets(n_routes: int, n_hops: int, p_pad: int) -> tuple[int, int]:
    """The relay route and hop counts a padded batch is timed at.

    A batch without relays keeps none (``R = H = 0``: the route loops
    vanish).  Otherwise routes pad to the plane bucket, or the next power
    of two above it (a self-relay takes one plane, so a step's routes
    rarely outnumber its planes), and hops to a power of two: the counts
    follow each grid's decisions, and one program then times every grid
    of a sweep whose plans relay.
    """
    if n_routes == 0:
        return 0, 0
    return max(_bucket(n_routes), p_pad), _bucket(n_hops)


def pad_packed(
    packed: dict[str, np.ndarray],
    b_pad: int,
    s_pad: int,
    p_pad: int,
    r_pad: int | None = None,
    h_pad: int | None = None,
) -> dict[str, np.ndarray]:
    """Pad a packed batch to ``(b_pad, s_pad, p_pad)`` bucket shapes, and
    its relay routes and hops to ``r_pad`` and ``h_pad`` where given.

    Padded batch rows / steps / planes carry zero volume and False masks,
    so the recurrence leaves them inert; padded bandwidth is 1.0 (never
    used, but keeps ``volume / bw`` NaN-free); a padded route carries no
    volume over no plane.
    """
    b, s, p = packed["vol"].shape
    _, _, r, h = packed["byp_plane"].shape
    r_pad = r if r_pad is None else r_pad
    h_pad = h if h_pad is None else h_pad
    if (b, s, p, r, h) == (b_pad, s_pad, p_pad, r_pad, h_pad):
        return packed
    from repro.core.ir.engine import NO_CONFIG

    out: dict[str, np.ndarray] = {}
    fill = {
        "vol": 0.0,
        "step_vol": 0.0,
        "step_cfg": NO_CONFIG,
        "step_mask": False,
        "plane_mask": False,
        "bw": 1.0,
        "init": NO_CONFIG,
        "t_recfg": 0.0,
        "chain": False,
        "ready": 0.0,
        "byp_vol": 0.0,
        "byp_plane": -1,
    }
    tgt_shape = {
        "vol": (b_pad, s_pad, p_pad),
        "step_vol": (b_pad, s_pad),
        "step_cfg": (b_pad, s_pad),
        "step_mask": (b_pad, s_pad),
        "plane_mask": (b_pad, p_pad),
        "bw": (b_pad, p_pad),
        "init": (b_pad, p_pad),
        "t_recfg": (b_pad,),
        "chain": (b_pad,),
        "ready": (b_pad, p_pad),
        # Route/hop counts are decision-determined: they pad where the
        # caller asks (`relay_buckets`), and bypass-free sweeps keep
        # R = H = 0.
        "byp_vol": (b_pad, s_pad, r_pad),
        "byp_plane": (b_pad, s_pad, r_pad, h_pad),
    }
    for key, arr in packed.items():
        padded = np.full(tgt_shape[key], fill[key], dtype=arr.dtype)
        padded[tuple(slice(0, d) for d in arr.shape)] = arr
        out[key] = padded
    return out


# ---------------------------------------------------------------------------
# NumPy reference backend
# ---------------------------------------------------------------------------
def _timing_numpy(
    p: dict[str, np.ndarray], attribution: bool = False
) -> BatchResult:
    """Earliest-start timing over the packed batch, one step per loop turn.

    Per-plane update order matches the object executor exactly (bypass
    relay hops first, riding installed configs; then lazy reconfigures at
    plane-free; transmissions at ``max(barrier, free)`` in CHAIN mode or
    plane-free in INDEPENDENT mode), so per-instance CCTs are bitwise
    identical to ``repro.core.simulator.execute``.
    """
    b, s_max, n_p = p["vol"].shape
    n_routes = p["byp_vol"].shape[2]
    n_hops = p["byp_plane"].shape[3]
    rows = np.arange(b)
    free = p["ready"].copy()
    held = p["init"].copy()
    barrier = np.zeros(b)
    cct = np.zeros(b)
    busy = np.zeros_like(free)
    n_recfg = np.zeros(b, dtype=np.int64)
    feasible = np.ones(b, dtype=bool)
    volume_ok = np.ones(b, dtype=bool)
    t_recfg = p["t_recfg"][:, None]
    chain = p["chain"][:, None]
    att_xmit = att_byp = att_wait = att_hidden = None
    if attribution:
        att_xmit = np.zeros((b, s_max, n_p))
        att_byp = np.zeros((b, s_max, n_p))
        att_wait = np.zeros((b, s_max, n_p))
        att_hidden = np.zeros((b, s_max, n_p))
    for i in range(s_max):
        v = p["vol"][:, i, :]
        live = p["step_mask"][:, i]
        active = (v > EPS_VOLUME) & p["plane_mask"] & live[:, None]
        has = active.any(axis=1)
        # Bypass relays run first (they ride installed configs, before
        # this step's direct traffic forces reconfigurations): serialized
        # store-and-forward hops, each occupying its plane's link.
        byp_end = np.full(b, -np.inf)
        has_byp = np.zeros(b, dtype=bool)
        sent_byp = np.zeros(b)
        for r in range(n_routes):
            rv = p["byp_vol"][:, i, r]
            route_live = (rv > EPS_VOLUME) & live
            if not route_live.any():
                continue
            has_byp |= route_live
            sent_byp += np.where(route_live, rv, 0.0)
            prev_end = np.where(p["chain"], barrier, 0.0)
            for h in range(n_hops):
                j = p["byp_plane"][:, i, r, h]
                upd = route_live & (j >= 0)
                jj = np.clip(j, 0, n_p - 1)
                free_j = free[rows, jj]
                start = np.maximum(prev_end, free_j)
                end = start + rv / p["bw"][rows, jj]
                free[rows, jj] = np.where(upd, end, free_j)
                busy[rows, jj] += np.where(upd, end - start, 0.0)
                if attribution:
                    # One hop touches one plane per row, so the fancy
                    # index has no duplicates within this statement.
                    att_byp[rows, i, jj] += np.where(upd, end - start, 0.0)
                prev_end = np.where(upd, end, prev_end)
            byp_end = np.maximum(
                byp_end, np.where(route_live, prev_end, -np.inf)
            )
        feasible &= ~(
            live
            & (p["step_vol"][:, i] > EPS_VOLUME)
            & ~has
            & ~has_byp
        )
        # Volume conservation (the object validator's Eq. 1 check, with
        # the shared tolerance formula); routes deliver once per route.
        sent = np.where(active, v, 0.0).sum(axis=1) + sent_byp
        cons_tol = np.maximum(
            TOL, REL_TOL * np.maximum(p["step_vol"][:, i], 1.0)
        )
        volume_ok &= ~live | (
            np.abs(sent - p["step_vol"][:, i]) <= cons_tol
        )
        cfg = p["step_cfg"][:, i][:, None]
        need = active & (held != cfg)
        free_before = free  # post-bypass, pre-reconfiguration plane state
        free = np.where(need, free + t_recfg, free)
        held = np.where(need, cfg, held)
        busy += np.where(need, t_recfg, 0.0)
        n_recfg += need.sum(axis=1)
        start = np.where(chain, np.maximum(barrier[:, None], free), free)
        end = start + v / p["bw"]
        if attribution:
            # Exposed reconfiguration: how much the reconfigure delayed
            # this plane's transmission beyond the barrier it would have
            # waited at anyway; the rest of t_recfg ran hidden under the
            # previous step's window (the paper's overlap, measured).
            start_nr = np.where(
                chain, np.maximum(barrier[:, None], free_before), free_before
            )
            wait = np.where(need, start - start_nr, 0.0)
            att_wait[:, i, :] = wait
            att_hidden[:, i, :] = np.where(need, t_recfg - wait, 0.0)
            att_xmit[:, i, :] = np.where(active, end - start, 0.0)
        free = np.where(active, end, free)
        busy += np.where(active, end - start, 0.0)
        step_end = np.where(active, end, -np.inf).max(axis=1, initial=-np.inf)
        step_end = np.maximum(step_end, byp_end)
        has_any = has | has_byp
        barrier = np.where(has_any, np.maximum(barrier, step_end), barrier)
        cct = np.where(has_any, np.maximum(cct, step_end), cct)
    return finalize_result(
        cct,
        n_recfg,
        busy,
        feasible,
        volume_ok,
        p["plane_mask"],
        attribution=(
            (att_xmit, att_byp, att_wait, att_hidden) if attribution else None
        ),
        step_mask=p["step_mask"] if attribution else None,
    )


class NumpyBackend(TimingBackend):
    """Reference backend: vectorized NumPy, one loop turn per step."""

    name = "numpy"

    def derive_timing(
        self, packed: dict[str, np.ndarray], attribution: bool = False
    ) -> BatchResult:
        return _timing_numpy(packed, attribution=attribution)


# ---------------------------------------------------------------------------
# JAX backend: jit + lax.scan over padded buckets
# ---------------------------------------------------------------------------
def _require_jax():
    try:
        import jax  # noqa: F401  (availability probe)
    except Exception as exc:  # pragma: no cover - env without jax
        raise BackendUnavailable(
            "the 'jax' IR backend needs jax installed (pip install jax)"
        ) from exc
    return jax


def x64():
    """Context manager scoping 64-bit floats and ints for device programs.

    Every jax, Pallas and fused-planner entry point runs under it: the
    parity contract is with the float64 numpy reference, while the
    process-wide JAX default stays 32-bit for everything else.
    """
    return _require_jax().enable_x64(True)


def _build_jax_timing(attribution: bool = False) -> Callable:
    """The scan-lowered recurrence (built lazily so numpy users never
    import jax).

    With ``attribution=True`` the scan additionally emits the per-step
    component rows as ``ys`` -- stacked to (S, B, P) and transposed on
    device -- from the same traced expressions the carry update uses, so
    components match the scalar outputs float-for-float.  A separate
    traced program per flag keeps the default path's compiled code
    untouched.
    """
    jax = _require_jax()
    import jax.numpy as jnp

    # Compiled as ``jit_fn``: the program name the benchmark's
    # ``timing_scan_ms`` reads (pinned by tests/test_trace_spans.py).
    def fn(
        vol, step_vol, step_cfg, step_mask, plane_mask, bw, init,
        t_recfg, chain, ready, byp_vol, byp_plane,
    ):
        b, _, n_p = vol.shape
        n_routes = byp_vol.shape[2]
        n_hops = byp_plane.shape[3]
        t_recfg_c = t_recfg[:, None]
        chain_c = chain[:, None]
        plane_iota = jnp.arange(n_p)[None, :]

        def body(carry, xs):
            free, held, barrier, cct, busy, n_recfg, feasible, volume_ok = (
                carry
            )
            v, live, svol, scfg, bv, bp = xs
            active = (v > EPS_VOLUME) & plane_mask & live[:, None]
            has = jnp.any(active, axis=1)
            # Bypass relays first (installed configs, store-and-forward
            # hop serialization) -- the route/hop loops unroll at trace
            # time (R and H are small, 0 for bypass-free sweeps).
            byp_end = jnp.full(b, -jnp.inf, free.dtype)
            has_byp = jnp.zeros(b, bool)
            sent_byp = jnp.zeros(b, free.dtype)
            att_byp = jnp.zeros_like(free)
            for r in range(n_routes):
                rv = bv[:, r]
                route_live = (rv > EPS_VOLUME) & live
                has_byp = has_byp | route_live
                sent_byp = sent_byp + jnp.where(route_live, rv, 0.0)
                prev_end = jnp.where(chain, barrier, 0.0)
                for h in range(n_hops):
                    j = bp[:, r, h]
                    upd = route_live & (j >= 0)
                    jj = jnp.clip(j, 0, n_p - 1)
                    mask = (plane_iota == jj[:, None]) & upd[:, None]
                    free_j = jnp.take_along_axis(
                        free, jj[:, None], axis=1
                    )[:, 0]
                    start = jnp.maximum(prev_end, free_j)
                    end = start + rv / jnp.take_along_axis(
                        bw, jj[:, None], axis=1
                    )[:, 0]
                    free = jnp.where(mask, end[:, None], free)
                    busy = busy + jnp.where(
                        mask, (end - start)[:, None], 0.0
                    )
                    if attribution:
                        att_byp = att_byp + jnp.where(
                            mask, (end - start)[:, None], 0.0
                        )
                    prev_end = jnp.where(upd, end, prev_end)
                byp_end = jnp.maximum(
                    byp_end, jnp.where(route_live, prev_end, -jnp.inf)
                )
            feasible = feasible & ~(
                live & (svol > EPS_VOLUME) & ~has & ~has_byp
            )
            sent = jnp.where(active, v, 0.0).sum(axis=1) + sent_byp
            cons_tol = jnp.maximum(TOL, REL_TOL * jnp.maximum(svol, 1.0))
            volume_ok = volume_ok & (
                ~live | (jnp.abs(sent - svol) <= cons_tol)
            )
            cfg = scfg[:, None]
            need = active & (held != cfg)
            free_before = free
            free = jnp.where(need, free + t_recfg_c, free)
            held = jnp.where(need, cfg, held)
            busy = busy + jnp.where(need, t_recfg_c, 0.0)
            n_recfg = n_recfg + need.sum(axis=1)
            start = jnp.where(
                chain_c, jnp.maximum(barrier[:, None], free), free
            )
            end = start + v / bw
            ys = None
            if attribution:
                start_nr = jnp.where(
                    chain_c,
                    jnp.maximum(barrier[:, None], free_before),
                    free_before,
                )
                wait = jnp.where(need, start - start_nr, 0.0)
                ys = (
                    jnp.where(active, end - start, 0.0),
                    att_byp,
                    wait,
                    jnp.where(need, t_recfg_c - wait, 0.0),
                )
            free = jnp.where(active, end, free)
            busy = busy + jnp.where(active, end - start, 0.0)
            step_end = jnp.max(
                jnp.where(active, end, -jnp.inf), axis=1, initial=-jnp.inf
            )
            step_end = jnp.maximum(step_end, byp_end)
            has_any = has | has_byp
            barrier = jnp.where(
                has_any, jnp.maximum(barrier, step_end), barrier
            )
            cct = jnp.where(has_any, jnp.maximum(cct, step_end), cct)
            return (
                free, held, barrier, cct, busy, n_recfg, feasible, volume_ok
            ), ys

        carry = (
            ready,
            init,
            jnp.zeros(b, ready.dtype),
            jnp.zeros(b, ready.dtype),
            jnp.zeros_like(ready),
            jnp.zeros(b, init.dtype),
            jnp.ones(b, bool),
            jnp.ones(b, bool),
        )
        xs = (
            jnp.swapaxes(vol, 0, 1),  # (S, B, P)
            step_mask.T,
            step_vol.T,
            step_cfg.T,
            jnp.swapaxes(byp_vol, 0, 1),  # (S, B, R)
            jnp.swapaxes(byp_plane, 0, 1),  # (S, B, R, H)
        )
        (free, held, barrier, cct, busy, n_recfg, feasible, volume_ok), ys = (
            jax.lax.scan(body, carry, xs)
        )
        if attribution:
            # ys arrive stacked (S, B, P); batch-major like everything else.
            return (cct, n_recfg, busy, feasible, volume_ok) + tuple(
                jnp.moveaxis(y, 0, 1) for y in ys
            )
        return cct, n_recfg, busy, feasible, volume_ok

    return jax.jit(fn)


class JaxBackend(TimingBackend):
    """jit + scan over power-of-two padded buckets (CPU or accelerator)."""

    name = "jax"

    def __init__(self) -> None:
        _require_jax()
        # One compiled program per attribution flag (the ys outputs
        # change the traced computation's signature).
        self._fns: dict[bool, Callable] = {}

    def _padded(self, packed: dict[str, np.ndarray]):
        # Bucket the dimensions that vary continuously with sweep size
        # (batch, planes) and with the plans (relay routes and hops); the
        # step count is pattern-determined, so its distinct values are
        # few and padding it would only buy a copy of the (B, S, P)
        # volume tensor per call.
        b, s, p = packed["vol"].shape
        _, _, r, h = packed["byp_plane"].shape
        p_pad = _bucket(p)
        r_pad, h_pad = relay_buckets(r, h, p_pad)
        return pad_packed(packed, _bucket(b), s, p_pad, r_pad, h_pad), (b, p)

    def derive_timing(
        self, packed: dict[str, np.ndarray], attribution: bool = False
    ) -> BatchResult:
        fn = self._fns.get(attribution)
        if fn is None:
            fn = self._fns[attribution] = _build_jax_timing(attribution)
        padded, (b, p) = self._padded(packed)
        with x64():
            out = fn(
                padded["vol"], padded["step_vol"], padded["step_cfg"],
                padded["step_mask"], padded["plane_mask"], padded["bw"],
                padded["init"], padded["t_recfg"], padded["chain"],
                padded["ready"], padded["byp_vol"], padded["byp_plane"],
            )
        cct, n_recfg, busy, feasible, volume_ok = out[:5]
        att = None
        if attribution:
            att = tuple(np.asarray(a)[:b, :, :p] for a in out[5:])
        return finalize_result(
            np.asarray(cct)[:b],
            np.asarray(n_recfg)[:b],
            np.asarray(busy)[:b, :p],
            np.asarray(feasible)[:b],
            np.asarray(volume_ok)[:b],
            packed["plane_mask"],
            attribution=att,
            step_mask=packed["step_mask"] if attribution else None,
        )


# ---------------------------------------------------------------------------
# Pallas backend: blocked-scan kernel (interpret mode off the TPU)
# ---------------------------------------------------------------------------
class PallasBackend(TimingBackend):
    """Blocked-scan Pallas kernel (`repro.kernels.timing_scan`).

    Compiled on the TPU, interpreted on every other platform (the path
    the CPU tests exercise).  ``interpret`` overrides that for tests.
    """

    name = "pallas"

    def __init__(self, interpret: bool | None = None) -> None:
        _require_jax()
        try:
            # Deferred so numpy-only users never import pallas; jax can
            # be importable while jax.experimental.pallas is not (old
            # jax), so this probe is wrapped too.
            from repro.kernels import timing_scan
        except Exception as exc:
            raise BackendUnavailable(
                "the 'pallas' IR backend needs a jax with a working "
                f"jax.experimental.pallas ({exc})"
            ) from exc

        self._kernel = timing_scan.timing_scan
        self._interpret_override = interpret

    @property
    def interpret(self) -> bool:
        if self._interpret_override is not None:
            return self._interpret_override
        import jax

        return jax.default_backend() != "tpu"

    def derive_timing(
        self, packed: dict[str, np.ndarray], attribution: bool = False
    ) -> BatchResult:
        b, s, p = packed["vol"].shape
        padded = pad_packed(packed, _bucket(b), s, _bucket(p))
        with x64():
            out = self._kernel(
                padded, interpret=self.interpret, attribution=attribution
            )
        cct, n_recfg, busy, feasible, volume_ok = out[:5]
        att = None
        if attribution:
            # Four component cubes straight from the kernel (xmit,
            # bypass, exposed wait, hidden), already in finalize order.
            att = tuple(np.asarray(a)[:b, :, :p] for a in out[5:])
        return finalize_result(
            np.asarray(cct)[:b],
            np.asarray(n_recfg)[:b],
            np.asarray(busy)[:b, :p],
            np.asarray(feasible)[:b],
            np.asarray(volume_ok)[:b],
            packed["plane_mask"],
            attribution=att,
            step_mask=packed["step_mask"] if attribution else None,
        )


# ---------------------------------------------------------------------------
# Registry + selection
# ---------------------------------------------------------------------------
BACKENDS: dict[str, type[TimingBackend]] = {
    "numpy": NumpyBackend,
    "jax": JaxBackend,
    "pallas": PallasBackend,
}

_instances: dict[str, TimingBackend] = {}


def get_backend(name: str) -> TimingBackend:
    """Instantiate (and cache) the named backend.

    Raises ``BackendUnavailable`` when the backend's dependencies are
    missing, ``ValueError`` for an unknown name.
    """
    if name not in BACKENDS:
        raise ValueError(
            f"unknown IR backend {name!r}; choose from "
            f"{sorted(BACKENDS)}"
        )
    if name not in _instances:
        _instances[name] = BACKENDS[name]()
    return _instances[name]


def default_backend_name() -> str:
    """The process-wide default (``REPRO_IR_BACKEND``, else numpy)."""
    return knobs.ir_backend()


def resolve_backend(
    backend: str | TimingBackend | None,
) -> TimingBackend:
    """Per-call selection: instance > name > env default."""
    if isinstance(backend, TimingBackend):
        return backend
    return get_backend(backend if backend is not None else
                       default_backend_name())


def available_backends() -> tuple[str, ...]:
    """Names of the backends whose dependencies import on this host."""
    names = []
    for name in BACKENDS:
        try:
            get_backend(name)
        except BackendUnavailable:
            continue
        names.append(name)
    return tuple(names)


# Batch size at and above which the grid planners (`swot_greedy_grid` /
# `plan_grid`) auto-select the jax backend for their scoring passes;
# small grids stay on numpy (jit dispatch does not amortize).  Override
# with the env var; <= 0 disables auto-selection.  (Both names are
# defined in `repro.core.knobs` and re-exported here for compat.)
ENV_GRID_BACKEND_THRESHOLD = knobs.ENV_GRID_BACKEND_THRESHOLD
DEFAULT_GRID_BACKEND_THRESHOLD = knobs.DEFAULT_GRID_BACKEND_THRESHOLD


def select_backend_by_size(
    n_rows: int,
    env_var: str,
    default_threshold: int,
    explicit: "str | TimingBackend | None" = None,
) -> "str | TimingBackend | None":
    """Threshold-based jax auto-selection for batched evaluation passes.

    The single policy shared by the runtime arbiter's lease re-scoring
    and the grid planners: an ``explicit`` backend always wins; otherwise
    jax is selected once the batch reaches the threshold read from
    ``env_var`` (falling back to ``default_threshold``) -- large batches
    amortize jit dispatch while small ones are faster on the numpy
    reference -- and ``None`` (the ``REPRO_IR_BACKEND`` env default) is
    returned when jax is unavailable or the threshold is not met.  A
    threshold <= 0 disables auto-selection.
    """
    if explicit is not None:
        return explicit
    threshold = knobs.int_knob(env_var, default_threshold)
    if threshold <= 0 or n_rows < threshold:
        return None
    try:
        get_backend("jax")
    except BackendUnavailable:
        # Large batch but no jax: fall through to the env default --
        # EXCEPT when that default is the pallas interpreter, which on a
        # large batch times the interpreter, not the kernel.  Route
        # those to the numpy reference instead (auto-selection must
        # never choose pallas-interpret for large batches).
        if default_backend_name() == "pallas":
            try:
                if get_backend("pallas").interpret:
                    return "numpy"
            except BackendUnavailable:
                pass
        return None
    return "jax"


# Grid-cell count at and above which the grid planners auto-select the
# FUSED on-device planner (`repro.core.ir.fused`): the whole per-step
# greedy loop as one jitted lax.scan.  Two defaults:
#
# * CHAIN sweep grids (``swot_greedy_grid`` / ``plan_grid``) of fewer
#   than DEFAULT_FUSED_PLANNER_THRESHOLD cells on a TPU switch at
#   TPU_CHAIN_FUSED_PLANNER_THRESHOLD, measured on one TPU v5e with the
#   program loaded (`benchmarks/planner_crossover.py`, PERF.md): there
#   the numpy loop's few thousand small host dispatches a grid cost more
#   than the scan (`tpu_chain_rule`).  Under the same rule a fused CHAIN
#   call pads the scan's cell axis to a power of two
#   (`fused.chain_cells_padded`), so each bucket compiles once; the first
#   grid of a bucket in a process pays that compile or a cache read.
# * Every other grid keeps DEFAULT_FUSED_PLANNER_THRESHOLD, set on the
#   CPU, unpadded: every other backend, INDEPENDENT grids, CHAIN grids of
#   DEFAULT_FUSED_PLANNER_THRESHOLD cells or more, and the arbiter's
#   batched grants (``swot_greedy_chain_batch``, which runs on the event
#   path and has no chip cell yet).
#
# Where the device's float64 is IEEE (the CPU) the two planners are
# bitwise-identical, so there the threshold is purely a performance
# knob.  On a TPU, whose float64 is emulated, the fused planner's split
# volumes differ in their last bits and rare near-tie choices flip, so a
# cell's plan depends on which planner its grid took (ROADMAP speed
# item 3).
# The env var overrides both defaults; <= 0 disables fused auto-selection.
ENV_FUSED_PLANNER_THRESHOLD = knobs.ENV_FUSED_PLANNER_THRESHOLD
DEFAULT_FUSED_PLANNER_THRESHOLD = knobs.DEFAULT_FUSED_PLANNER_THRESHOLD
TPU_CHAIN_FUSED_PLANNER_THRESHOLD = knobs.TPU_CHAIN_FUSED_PLANNER_THRESHOLD


def tpu_chain_rule(n_cells: int) -> bool:
    """Whether a CHAIN grid of ``n_cells`` falls under the chip's rule:
    fewer than DEFAULT_FUSED_PLANNER_THRESHOLD cells, and this process's
    JAX runs on a TPU.

    False without importing JAX where nothing has imported it yet, so a
    plan that stays on numpy never starts the XLA runtime for this.
    """
    if n_cells >= DEFAULT_FUSED_PLANNER_THRESHOLD:
        return False
    jax = sys.modules.get("jax")
    return jax is not None and jax.default_backend() == "tpu"


def select_planner_by_size(
    n_cells: int,
    explicit: str | None = None,
    default_threshold: int = DEFAULT_FUSED_PLANNER_THRESHOLD,
) -> str:
    """Threshold policy for the grid planner implementation.

    Returns ``"fused"`` (one-program ``lax.scan`` planner) once the grid
    reaches ``REPRO_FUSED_PLANNER_THRESHOLD`` cells (falling back to
    ``default_threshold``) and jax is importable, else ``"step"`` (the
    per-step numpy loop).  An ``explicit`` planner always wins; a
    threshold <= 0 disables auto-selection.
    """
    if explicit is not None:
        if explicit not in ("step", "fused"):
            raise ValueError(
                f"unknown planner {explicit!r}; choose 'step' or 'fused'"
            )
        return explicit
    threshold = knobs.fused_planner_threshold(default_threshold)
    if threshold <= 0 or n_cells < threshold:
        return "step"
    try:
        get_backend("jax")
    except BackendUnavailable:
        return "step"
    return "fused"
