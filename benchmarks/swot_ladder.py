"""Optical-layer hillclimb: scheduling iterations on the MoE cell's
profiled collectives (the paper's own technique, then beyond it).

Ladder per collective of one qwen2-moe-a2.7b train step on 16 endpoints
x 4 optical planes (TPU-calibrated: 50 GB/s links, 200 us reconfig):

    strawman-ICR -> SWOT chain (paper) -> SWOT independent (beyond paper,
    pairwise only) -> 8 planes (provisioning sensitivity)

CCT per iteration; the EXPERIMENTS.md Perf log quotes this table.
"""

from repro.configs.base import shape_cell
from repro.configs.registry import get_config
from repro.core import (
    DependencyMode,
    OpticalFabric,
    TPU_V5E_LINK_BANDWIDTH,
    batch_evaluate,
    get_pattern,
    ideal_cct,
    prestage_for,
    strawman_instance,
    swot_greedy,
)
from repro.core.planner import profile_train_step
from repro.models.lm import _decoder_specs
from jax.sharding import AbstractMesh
from repro.sharding.rules import MeshContext


def run() -> list[tuple[str, float, str]]:
    cfg = get_config("qwen2_moe_a2_7b").replace(
        moe_token_slice=True, sequence_parallel=True
    )
    mesh = AbstractMesh((16, 16), ("data", "model"))
    ctx = MeshContext(mesh=mesh, dp_axes=("data",))
    cell = shape_cell("train_4k")
    specs = _decoder_specs(cfg, ctx)
    requests = profile_train_step(cfg, ctx, cell, specs)

    cells = []
    for req in requests:
        pattern = get_pattern(req.algorithm, req.n_nodes, req.size)
        for planes in (4, 8):
            fabric = prestage_for(
                OpticalFabric(
                    req.n_nodes,
                    planes,
                    bandwidth=TPU_V5E_LINK_BANDWIDTH,
                    t_recfg=200e-6,
                ),
                pattern,
            )
            cells.append((req, planes, fabric, pattern))

    # Every cell's strawman baseline in ONE batched IR pass (the timing
    # backend follows REPRO_IR_BACKEND: numpy default, jax/pallas opt-in).
    straw_ccts = batch_evaluate(
        [
            strawman_instance(fabric, pattern)
            for _, _, fabric, pattern in cells
        ]
    ).cct

    rows = []
    for (req, planes, fabric, pattern), straw in zip(cells, straw_ccts):
        straw = float(straw)
        chain = swot_greedy(fabric, pattern)
        entries = [
            ("strawman", straw),
            ("swot_chain", chain.cct),
        ]
        if req.algorithm == "pairwise_alltoall":
            indep = swot_greedy(
                fabric, pattern, mode=DependencyMode.INDEPENDENT
            )
            entries.append(("swot_independent", indep.cct))
        ideal = ideal_cct(fabric, pattern)
        for mode, cct in entries:
            rows.append(
                (
                    f"swot_ladder_{req.tag}_{planes}pl_{mode}",
                    cct * 1e6,
                    f"ideal={ideal * 1e6:.1f}us "
                    f"size={req.size / 1e6:.1f}MB "
                    f"vs_strawman={1 - cct / straw:+.1%}",
                )
            )
    return rows


if __name__ == "__main__":
    for name, us, note in run():
        print(f"{name},{us:.1f},{note}")
