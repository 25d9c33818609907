import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax fixes the host device count at
first init, and the production meshes need 512 placeholder devices.

For every assigned architecture x its applicable shapes, on the 16x16
single-pod mesh AND the 2x16x16 multi-pod mesh:

    with mesh:
        lowered  = jax.jit(step_fn).lower(*abstract_inputs)
        compiled = lowered.compile()
        memory_analysis / cost_analysis / HLO walker -> roofline terms

No arrays are ever allocated: params, optimizer state, batches and KV
caches are ShapeDtypeStructs carrying NamedShardings from the rules
engine.  Results land in ``artifacts/dryrun/<arch>__<shape>__<mesh>.json``
(incremental: existing artifacts are skipped unless --force).

Usage:
    python -m repro.launch.dryrun [--arch qwen3_4b] [--shape train_4k]
        [--mesh single|multi|both] [--force] [--report]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis import constants as hw
from repro.analysis.hlo import analyze_hlo_text
from repro.analysis.roofline import (
    model_flops_for,
    roofline_from_summary,
)
from repro.configs.base import ArchConfig, ShapeCell
from repro.configs.inputs import input_specs
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import production_context
from repro.models.common import is_spec
from repro.models.lm import build_model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.sharding.rules import MeshContext, param_partition_specs

ARTIFACT_DIR = os.path.join("artifacts", "dryrun")


def _abstract(ctx: MeshContext, spec_tree, fsdp: bool):
    parts = param_partition_specs(ctx, spec_tree, fsdp=fsdp)
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(ctx.mesh, p)
        ),
        spec_tree,
        parts,
        is_leaf=is_spec,
    )


def _abstract_batch(ctx: MeshContext, specs: dict):
    out = {}
    for name, s in specs.items():
        axes = ("batch",) + (None,) * (len(s.shape) - 1)
        out[name] = jax.ShapeDtypeStruct(
            s.shape,
            s.dtype,
            sharding=ctx.sharding_for(s.shape, axes),
        )
    return out


def _step_and_inputs(cfg: ArchConfig, ctx: MeshContext, cell: ShapeCell):
    model = build_model(cfg, ctx)
    if cell.kind == "train":
        from repro.train.loop import TrainState, make_train_step

        step_fn, _sh = make_train_step(
            model, AdamWConfig(), grad_accum=cfg.grad_accum
        )
        params = _abstract(ctx, model.specs, cfg.fsdp_params)
        opt = jax.eval_shape(adamw_init, params)
        # Re-attach shardings (eval_shape drops them).
        opt = {
            "m": _abstract(ctx, model.specs, cfg.fsdp_params),
            "v": _abstract(ctx, model.specs, cfg.fsdp_params),
            "count": jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=NamedSharding(ctx.mesh, P())
            ),
        }
        state = TrainState(
            params=params,
            opt=opt,
            step=jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=NamedSharding(ctx.mesh, P())
            ),
        )
        batch = _abstract_batch(ctx, input_specs(cfg, cell))
        return step_fn, (state, batch), model
    if cell.kind == "prefill":
        params = _abstract(ctx, model.specs, cfg.fsdp_params)
        batch = _abstract_batch(ctx, input_specs(cfg, cell))
        return model.prefill, (params, batch), model
    # decode
    params = _abstract(ctx, model.specs, cfg.fsdp_params)
    cache_specs = model.cache_specs(cell.global_batch, cell.seq_len)
    cache = _abstract(ctx, cache_specs, fsdp=False)
    tokens = jax.ShapeDtypeStruct(
        (cell.global_batch, 1),
        jnp.int32,
        sharding=ctx.sharding_for((cell.global_batch, 1), ("batch", None)),
    )
    return model.decode_step, (params, cache, tokens), model


def run_cell(
    cfg: ArchConfig,
    cell: ShapeCell,
    multi_pod: bool,
    verbose: bool = True,
) -> dict:
    mesh_name = "pods2" if multi_pod else "pod1"
    ctx = production_context(multi_pod=multi_pod)
    chips = ctx.mesh.size
    t0 = time.time()
    step_fn, inputs, model = _step_and_inputs(cfg, ctx, cell)
    with jax.set_mesh(ctx.mesh):
        lowered = jax.jit(step_fn, donate_argnums=(0,)).lower(*inputs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):  # older JAX: one dict per device
            cost = cost[0] if cost else {}
        summary = analyze_hlo_text(compiled.as_text())
    model_flops = model_flops_for(cfg, cell, model.specs)
    roof = roofline_from_summary(
        cfg.name, cell, mesh_name, chips, summary, model_flops
    )
    device_bytes = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    record = {
        "arch": cfg.name,
        "shape": cell.name,
        "mesh": mesh_name,
        "chips": chips,
        "status": "ok",
        "fits_hbm": bool(device_bytes <= hw.HBM_BYTES),
        "device_bytes": int(device_bytes),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "xla_cost_flops_per_device": float(cost.get("flops", 0.0)),
        "walker_flops_per_device": summary.flops,
        "walker_bytes_per_device": summary.bytes_accessed,
        "collective_bytes_per_device": summary.collective_bytes,
        "collective_by_kind": {
            k: float(v) for k, v in summary.collective_by_kind.items()
        },
        "collective_counts": summary.collective_counts,
        "while_trip_counts": summary.while_trip_counts,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "roofline": roof.row(),
    }
    if verbose:
        print(
            f"[{cfg.name:22s} {cell.name:11s} {mesh_name:5s}] "
            f"compile={t_compile:6.1f}s dev_mem={device_bytes / 2**30:6.2f}GiB "
            f"fits={record['fits_hbm']} "
            f"dom={roof.dominant:10s} bound={roof.bound_s * 1e3:8.2f}ms "
            f"roofline_frac={roof.roofline_fraction:6.1%}",
            flush=True,
        )
    return record


def artifact_path(arch: str, shape: str, mesh_name: str) -> str:
    return os.path.join(
        ARTIFACT_DIR, f"{arch}__{shape}__{mesh_name}.json"
    )


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--arch", default=None)
    parser.add_argument("--shape", default=None)
    parser.add_argument(
        "--mesh", choices=("single", "multi", "both"), default="both"
    )
    parser.add_argument("--force", action="store_true")
    parser.add_argument(
        "--report", action="store_true", help="print roofline table only"
    )
    args = parser.parse_args()

    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    meshes = {
        "single": [False],
        "multi": [True],
        "both": [False, True],
    }[args.mesh]

    if args.report:
        _report()
        return

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        for cell in cfg.shapes():
            if args.shape and cell.name != args.shape:
                continue
            for multi_pod in meshes:
                mesh_name = "pods2" if multi_pod else "pod1"
                path = artifact_path(cfg.name, cell.name, mesh_name)
                if os.path.exists(path) and not args.force:
                    print(f"skip (cached): {path}", flush=True)
                    continue
                try:
                    record = run_cell(cfg, cell, multi_pod)
                except Exception as e:  # record failures, keep going
                    record = {
                        "arch": cfg.name,
                        "shape": cell.name,
                        "mesh": mesh_name,
                        "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc(limit=8),
                    }
                    failures.append(record)
                    print(
                        f"[{cfg.name} {cell.name} {mesh_name}] "
                        f"FAILED: {record['error']}",
                        flush=True,
                    )
                with open(path, "w") as f:
                    json.dump(record, f, indent=2)
    if failures:
        print(f"\n{len(failures)} cell(s) failed")
        raise SystemExit(1)
    print("\nall requested dry-run cells compiled")


def _report() -> None:
    rows = []
    for name in sorted(os.listdir(ARTIFACT_DIR)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(ARTIFACT_DIR, name)) as f:
            rec = json.load(f)
        if rec.get("status") == "ok":
            rows.append(rec)
    header = (
        f"{'arch':22s} {'shape':11s} {'mesh':5s} {'dev_GiB':>8s} "
        f"{'compute_ms':>10s} {'memory_ms':>9s} {'coll_ms':>8s} "
        f"{'dominant':>10s} {'useful':>7s} {'roof%':>6s}"
    )
    print(header)
    print("-" * len(header))
    for rec in rows:
        r = rec["roofline"]
        print(
            f"{rec['arch']:22s} {rec['shape']:11s} {rec['mesh']:5s} "
            f"{rec['device_bytes'] / 2**30:8.2f} "
            f"{r['compute_s'] * 1e3:10.2f} {r['memory_s'] * 1e3:9.2f} "
            f"{r['collective_s'] * 1e3:8.2f} {r['dominant']:>10s} "
            f"{r['useful_ratio']:7.2f} {r['roofline_fraction']:6.1%}"
        )


if __name__ == "__main__":
    main()
