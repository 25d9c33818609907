"""Fleet replay: training jobs of several tenants share one optical fabric.

Each tenant is a synchronous training job on every node of the fabric.
Its step is compute, then the step's collectives: when the compute ends
it submits its mix (DP gradient all-reduce of the parameters' bf16
bytes, TP all-gather of one activation buffer, an MoE model's expert
all-to-all of one capacity-shaped buffer) at once, and its next step's
compute starts when the last of them has finished.  A step's compute
lasts ``6 x active parameters x tokens`` FLOPs over the nodes' bf16 peak
at the configuration's model FLOPs utilisation.  ``--seed`` draws each
tenant's start within its first step; sizes and step times are the
configuration's own, so every seed offers the same work, interleaved
differently.

The harness builds the program's ``SimEngine`` and ``FabricArbiter`` as
``replay(stream=True)`` does, with an empty plan cache (a restarted
arbiter), and drives ``engine.step()`` until the window ends.  An event
served from the plan cache takes well under a millisecond, too short for
the host clock to time one by one, so the cell's end-to-end metric is
the jobs finished over the whole window.

Set-up runs the device program of the lease re-scoring once for every
shape this fabric and mix can ask (`rescoring_shapes`), and one training
step of every tenant on a separate, discarded arbiter and cache.

The check, after the window: no tenant starts another step, the engine
runs until every submitted job has finished, and then, against the
plain references:

* ``jobs_unfinished``: jobs submitted in the window that never finished;
* ``timeline_violation``: the fabric's timeline rebuilt from every plan
  installed, fresh or from the cache, across all jobs
  (`reference.timeline`): planes used by one job at a time, configs,
  durations, volumes and step order;
* ``rescoring_gap``: the device's CCT of each re-scored release candidate
  against the reference executor (relative).
"""

from __future__ import annotations

import math
import time

import numpy as np

from reference import chain, timeline
from reference.patterns import steps_of

BF16 = 2


# -- the tenants' training steps --------------------------------------------
def _attention(m: dict) -> int:
    d, head = m["d_model"], m["head_dim"]
    return d * (m["n_heads"] * head + 2 * m["n_kv_heads"] * head) + (
        m["n_heads"] * head * d
    )


def param_bytes(m: dict) -> float:
    """bf16 bytes of a model's parameters, from its dimensions."""
    d = m["d_model"]
    per_layer = _attention(m) + 3 * d * m["d_ff"]  # SwiGLU
    if m.get("n_experts"):
        per_layer += m["n_experts"] * 3 * d * m["moe_d_ff"]
    return float(m["n_layers"] * per_layer + m["vocab_size"] * d) * BF16


def active_params(m: dict) -> float:
    """Parameters one token passes through: attention, the dense FFN, the
    ``top_k`` routed experts and the tied output projection."""
    d = m["d_model"]
    per_layer = _attention(m) + 3 * d * m["d_ff"]
    if m.get("n_experts"):
        per_layer += m["top_k"] * 3 * d * m["moe_d_ff"]
    return float(m["n_layers"] * per_layer + m["vocab_size"] * d)


def request_mix(m: dict, n_nodes: int, tokens: int) -> list[tuple]:
    """(algorithm, nodes, bytes, tag) of one training step's collectives."""
    mix = [
        ("rabenseifner_allreduce", n_nodes, param_bytes(m),
         f"{m['name']}:dp_grad_sync"),
        ("all_gather", n_nodes, float(tokens * m["d_model"] * BF16),
         f"{m['name']}:tp_act_sync"),
    ]
    if m.get("n_experts"):
        capacity = int(tokens * m["top_k"] * m["capacity_factor"])
        mix.append(("pairwise_alltoall", n_nodes,
                    float(capacity * m["d_model"] * BF16),
                    f"{m['name']}:moe_ep_alltoall"))
    return mix


def step_seconds(m: dict, config: dict) -> float:
    """Compute time of one training step of tenant ``m``."""
    t = config["training"]
    flops = 6.0 * active_params(m) * config["tokens_per_step"]
    rate = config["n_nodes"] * t["node_peak_flops_per_s"] * t["mfu"]
    return flops / rate


class Tenant:
    def __init__(self, m: dict, config: dict) -> None:
        self.name = m["name"]
        self.mix = request_mix(m, config["n_nodes"], config["tokens_per_step"])
        self.step_s = step_seconds(m, config)
        self.step_lists = [steps_of(a, n, size) for a, n, size, _ in self.mix]
        self.outstanding = 0


def bucket(n: int) -> int:
    """The jax backend's padded size of a batch or plane count."""
    return 1 << max(0, (n - 1).bit_length())


def rescoring_shapes(n_planes: int, longest: int) -> list[tuple]:
    """(rows, steps, planes) of every re-scoring batch the arbiter can
    send the device on this fabric, each dimension at the jax backend's
    padded size.

    A job re-scores at a step boundary (1 to ``longest - 1`` steps
    remain) the ways to release some of its leased planes, 2 or more
    candidates, each keeping 1 to ``lease - 1`` planes; every job whose
    boundary falls at the same instant joins the batch, and leases are
    disjoint, so the rows are at most the largest sum of
    ``C(lease, lease // 2)`` over leases of 2 or more planes that share
    the fabric."""
    most = [0] * (n_planes + 1)
    for p in range(2, n_planes + 1):
        most[p] = max(
            math.comb(k, k // 2) + most[p - k] for k in range(2, p + 1)
        )

    rows = sorted({bucket(r) for r in range(2, most[n_planes] + 1)})
    planes = sorted({bucket(k) for k in range(1, n_planes)})
    return [
        (r, s, p) for p in planes for s in range(1, longest) for r in rows
    ]


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        self.config = config
        self.traffic = traffic
        self.seed = seed
        fab = config["fabric"]
        self.bandwidth = fab["link_gbps"] * 1e9 / 8
        self.window_s = 0.0
        self.counters: dict = {}
        self.installs: list[tuple] = []
        self.finish: dict = {}
        self.steps: dict = {}
        self.batches: list[dict] = []

    def _tenants(self) -> list[Tenant]:
        return [Tenant(m, self.config) for m in self.config["tenants"]]

    def _phases(self, tenants: list[Tenant]) -> list[float]:
        rng = np.random.default_rng([self.seed, 0xF1EE7])
        return [float(rng.uniform(0.0, t.step_s)) for t in tenants]

    # -- the program's objects ---------------------------------------------
    def _fabric(self):
        from repro.core import OpticalFabric

        fab = self.config["fabric"]
        return OpticalFabric(
            self.config["n_nodes"], fab["n_planes"], bandwidth=self.bandwidth,
            t_recfg=fab["t_recfg_s"],
        )

    def _start(self, tenants: list[Tenant], on_finish=None):
        """A fresh engine and arbiter (empty plan cache) with every
        tenant's first step scheduled; each step's collectives are
        submitted when its compute ends, the next step follows the last
        of them while ``self.feeding``."""
        from repro.core.shim import CollectiveRequest
        from repro.runtime import FabricArbiter, SimEngine

        engine = SimEngine()
        by_name = {t.name: t for t in tenants}

        def submit_step(t: Tenant) -> None:
            t.outstanding = len(t.mix)
            for (algo, nodes, size, tag), steps in zip(t.mix, t.step_lists):
                rec = arbiter.submit(CollectiveRequest(algo, nodes, size, tag),
                                     0, tenant=t.name)
                self.fed += 1
                self.steps[rec.job_id] = steps

        def sink(record) -> None:
            if on_finish is not None:
                on_finish(record)
            t = by_name[record.tenant]
            t.outstanding -= 1
            if t.outstanding == 0 and self.feeding:
                engine.at(engine.now + t.step_s, lambda t=t: submit_step(t))

        arbiter = FabricArbiter(
            engine, self._fabric(), keep_records=False, record_sink=sink,
            **self.config["arbiter"],
        )
        for t, phase in zip(tenants, self._phases(tenants)):
            engine.at(phase + t.step_s, lambda t=t: submit_step(t))
        return engine, arbiter

    # -- set-up -------------------------------------------------------------
    def _longest(self) -> int:
        return max(
            len(steps_of(a, n, 1.0)[0])
            for t in self._tenants() for a, n, _, _ in t.mix
        )

    def _compile_rescoring(self) -> int:
        """Run the re-scoring's device program once for each shape in
        `rescoring_shapes`."""
        from repro.core import OpticalFabric
        from repro.core.ir import BatchInstance, batch_evaluate
        from repro.core.patterns import get_pattern
        from repro.core.schedule import Decisions

        fab = self.config["fabric"]
        shapes = rescoring_shapes(fab["n_planes"], self._longest())
        pattern = get_pattern("pairwise_alltoall", self.config["n_nodes"], 1e6)
        for rows, steps, planes in shapes:
            fabric = OpticalFabric(self.config["n_nodes"], planes,
                                   bandwidth=self.bandwidth,
                                   t_recfg=fab["t_recfg_s"])
            sub = type(pattern)(pattern.name, pattern.n_nodes,
                                pattern.steps[:steps])
            dec = Decisions(tuple({0: s.volume} for s in sub.steps))
            batch_evaluate(
                [BatchInstance(fabric, sub, dec)] * rows,
                plane_ready=[(0.0,) * planes] * rows,
                backend=self.config["arbiter"]["backend"],
            )
        return len(shapes)

    def setup(self) -> dict:
        import scipy.optimize  # noqa: F401  (the host planner's LP solver)

        t0 = time.perf_counter()
        programs = self._compile_rescoring()
        t1 = time.perf_counter()
        # One training step of every tenant on a discarded arbiter.
        self.fed = 0
        self.feeding = False
        engine, _ = self._start(self._tenants())
        engine.run()
        self.steps.clear()
        return {
            "rescoring_shapes": programs,
            "rescoring_compile_s": t1 - t0,
            "warm_step_s": time.perf_counter() - t1,
        }

    # -- the window ---------------------------------------------------------
    def _record(self, arbiter) -> None:
        """Wrap the arbiter's plan installs and completions, and the
        arbiter module's re-scoring calls (until `_restore`), so that the
        check can read them; miss planning and re-scoring run under host
        spans of their own, which label the trace's idle gaps."""
        import jax

        import repro.runtime.arbiter as arb_mod

        driver = self
        annotate = jax.profiler.TraceAnnotation
        install = arbiter._install_plan
        complete = arbiter._complete
        build = arbiter._build_plan

        def install_plan(job, cached, t0):
            driver.installs.append((
                job.job_id, arbiter.engine.now, t0, job.planes,
                job.step_idx, job.key, cached.schedule,
            ))
            return install(job, cached, t0)

        def complete_job(job):
            driver.finish[job.job_id] = arbiter.engine.now
            return complete(job)

        def build_plan(job, plane_ready):
            with annotate("bench.miss_plan"):
                return build(job, plane_ready)

        arbiter._install_plan = install_plan
        arbiter._complete = complete_job
        arbiter._build_plan = build_plan

        evaluate = arb_mod.batch_evaluate
        plan_batch = arb_mod.swot_greedy_chain_batch

        def batch_evaluate(instances, plane_ready=None, backend=None, **kw):
            with annotate("bench.rescore"):
                result = evaluate(instances, plane_ready=plane_ready,
                                  backend=backend, **kw)
            driver.batches.append({
                "instances": list(instances),
                "ready": list(plane_ready),
                "cct": np.array(result.cct, dtype=np.float64),
                "feasible": np.array(result.feasible, dtype=bool),
            })
            return result

        def greedy_chain_batch(*args, **kw):
            with annotate("bench.miss_plan"):
                return plan_batch(*args, **kw)

        self._saved = (arb_mod, evaluate, plan_batch)
        arb_mod.batch_evaluate = batch_evaluate
        arb_mod.swot_greedy_chain_batch = greedy_chain_batch

    def _restore(self) -> None:
        arb_mod, evaluate, plan_batch = self._saved
        arb_mod.batch_evaluate = evaluate
        arb_mod.swot_greedy_chain_batch = plan_batch

    def window(self, seconds: float) -> None:
        import jax

        self.fed = 0
        self.feeding = True
        finished = [0]

        def on_finish(record) -> None:
            if record.finish is not None:  # a rejected job never finishes
                finished[0] += 1

        engine, arbiter = self._start(self._tenants(), on_finish)
        self._record(arbiter)
        clock = time.perf_counter
        annotate = jax.profiler.TraceAnnotation
        step = engine.step
        events = 0
        t_start = clock()
        with annotate("bench.window"):
            while True:
                more = step()
                events += 1
                if not more or clock() - t_start >= seconds:
                    break
        self.window_s = clock() - t_start
        self.feeding = False
        self.finished_in_window = finished[0]
        self.engine = engine
        stats = arbiter.plan_cache.stats
        self.counters = {
            "events": events,
            "window_s": self.window_s,
            "sim_s": engine.now,
            "cache_hits": int(stats.hits),
            "cache_misses": int(stats.misses),
            "plan_wall_s": float(stats.plan_wall_s),
            "jobs_fed": self.fed,
            "jobs_finished": self.finished_in_window,
            "device_batches": len(self.batches),
            "rescoring_shapes_used": len(self._shapes_used()),
        }

    def _shapes_used(self) -> set:
        return {
            (bucket(len(b["instances"])),
             max(len(i.pattern.steps) for i in b["instances"]),
             bucket(max(i.fabric.n_planes for i in b["instances"])))
            for b in self.batches
        }

    def summary(self) -> dict:
        return dict(self.counters)

    def end_to_end(self) -> dict:
        return {
            "replay_jobs_per_s": self.finished_in_window / self.window_s,
        }

    def context(self, trace: dict, peaks: dict):
        return Context(self, trace, peaks)

    def attempted_failed(self) -> tuple[int, int]:
        return self.fed, self.fed - len(self.finish)

    # -- the check ----------------------------------------------------------
    def _drain(self) -> None:
        """Run every submitted job to its end (no tenant starts a step)."""
        deadline = time.perf_counter() + self.traffic["drain_s"]
        while self.engine.step():
            if time.perf_counter() > deadline:
                break
        self._restore()

    def _rescoring_gap(self) -> float:
        """Worst gap between the device's CCT of a re-scored row and the
        reference executor's (relative)."""
        fab = self.config["fabric"]
        worst = 0.0
        for batch in self.batches:
            for r, (inst, ready) in enumerate(
                zip(batch["instances"], batch["ready"])
            ):
                if not batch["feasible"][r]:
                    continue
                f = inst.fabric
                cell = chain.Cell(
                    n_planes=f.n_planes, bandwidth=self.bandwidth,
                    t_recfg=fab["t_recfg_s"],
                    step_cfg=[s.config for s in inst.pattern.steps],
                    step_vol=[s.volume for s in inst.pattern.steps],
                )
                init = [
                    chain.NO_CONFIG if c is None else c
                    for c in (f.initial_configs or (None,) * f.n_planes)
                ]
                ref = chain.execute(cell, inst.decisions.splits,
                                    ready=ready, init=init)
                worst = max(worst, abs(batch["cct"][r] - ref) / ref)
        return worst

    def _timeline(self) -> list[timeline.Install]:
        """The recorded installs, each plan's activities read once."""
        acts: dict = {}
        out = []
        for job, at, t0, planes, step, key, schedule in self.installs:
            if id(schedule) not in acts:
                acts[id(schedule)] = tuple(
                    (a.plane, a.kind.name.lower(), a.step, a.start, a.end,
                     a.config, a.volume)
                    for a in schedule.activities
                )
            out.append(timeline.Install(
                job=job, at=at, t0=t0, planes=tuple(planes), base_step=step,
                key=key, activities=acts[id(schedule)],
            ))
        return out

    def check(self) -> list[tuple[str, float, float]]:
        limits = self.traffic["limits"]
        self._drain()
        unfinished = self.fed - len(self.finish)
        fab = self.config["fabric"]
        violation = timeline.violation(
            self._timeline(), self.finish, self.steps, self.bandwidth,
            fab["t_recfg_s"],
        )
        return [
            ("jobs_unfinished", float(unfinished), limits["jobs_unfinished"]),
            ("timeline_violation", violation, limits["timeline_violation"]),
            ("rescoring_gap", self._rescoring_gap(), limits["rescoring_gap"]),
        ]


class Context:
    """What a per-layer metric reader may read of a traced replay run."""

    def __init__(self, driver: Driver, trace: dict, peaks: dict) -> None:
        self.trace = trace
        self.counters = driver.counters
