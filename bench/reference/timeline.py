"""Plain check of a shared fabric's whole timeline, rebuilt from the plans
installed on it.

The arbiter gives each job a lease of physical planes and installs a plan
on it; at a lease change it cuts the plan at a step boundary and installs
a new one.  Here every install is taken as recorded -- the job, the
instant ``at`` it was installed, the plan's origin ``t0``, the leased
planes and the plan's activities in plan time on lease positions -- and
the timeline the fabric ran is rebuilt from them alone:

* a plan runs from its install until the job's next install (or its
  finish); of its activities, those that start before that cut ran, and
  an in-flight reconfiguration completes (optics cannot stop a mirror
  halfway); the rest were superseded and never ran;
* each plane starts with no config; a reconfiguration installs its
  job's config (namespaced by the job's algorithm and node count) when
  it ends.

On that timeline, for all jobs together:

* no two activities of one plane overlap, whichever jobs they belong to
  (this is what a plane granted twice, or a plan that ignores an earlier
  job's tail on a plane, breaks);
* a transmission runs only while its step's config is installed on its
  plane by its own job's namespace;
* a transmission lasts at least ``volume / bandwidth``, a
  reconfiguration at least ``t_recfg``;
* each job's leases never overlap another job's on any plane;
* each job carries every step's volume, and (CHAIN) starts a step only
  once every transmission of the step before it has ended.

`violation` returns the worst breach: a time breach over the duration of
the activity (or lease) it concerns, a volume breach over the step's
volume, and 1 for a transmission on the wrong config.  0 is a legal
timeline.
"""

from __future__ import annotations

import collections
import dataclasses

CUT_EPS = 1e-12  # s: an activity starting this close to a cut never ran


@dataclasses.dataclass(frozen=True)
class Install:
    job: int
    at: float  # engine time of the install (the previous plan's cut)
    t0: float  # origin of the plan's times
    planes: tuple  # physical plane of each lease position
    base_step: int  # the job's step the plan starts at
    key: tuple  # config namespace (algorithm, n_nodes)
    # (lease position, kind "xmit" | "recfg", plan step, start, end,
    # config, volume), plan-relative times
    activities: tuple


def violation(
    installs: list[Install],
    finish: dict,
    steps: dict,
    bandwidth: float,
    t_recfg: float,
) -> float:
    """Worst breach over the rebuilt timeline of ``installs``.

    ``finish`` maps a job to its finish time, ``steps`` maps it to its
    (config ids, bytes) per step from the collective's definition."""
    worst = 0.0

    def breach(amount: float, scale: float) -> None:
        nonlocal worst
        if amount > 0.0:
            worst = max(worst, amount / max(scale, 1e-12))

    by_job = collections.defaultdict(list)
    for ins in installs:
        by_job[ins.job].append(ins)
    ran = collections.defaultdict(list)  # plane -> activities that ran
    held = collections.defaultdict(list)  # plane -> (from, to, job)
    for job, plans in by_job.items():
        if job not in finish:
            return 1.0  # a job fed in the window that never finished
        plans.sort(key=lambda p: p.at)
        step_cfg, step_vol = steps[job]
        carried = collections.defaultdict(float)
        first = collections.defaultdict(lambda: float("inf"))
        last = collections.defaultdict(lambda: float("-inf"))
        for k, ins in enumerate(plans):
            cut = plans[k + 1].at if k + 1 < len(plans) else finish[job]
            for p in ins.planes:
                held[p].append((ins.at, cut, job))
            rel_cut = cut - ins.t0
            for pos, kind, step, start, end, config, volume in ins.activities:
                if start >= rel_cut - CUT_EPS:
                    continue  # superseded by the next install
                s = ins.base_step + step
                if not 0 <= pos < len(ins.planes) or not 0 <= s < len(step_vol):
                    return 1.0
                a0, a1 = ins.t0 + start, ins.t0 + end
                if kind == "recfg":
                    breach(t_recfg - (a1 - a0), t_recfg)
                else:
                    if config != step_cfg[s] or volume < 0.0:
                        return 1.0
                    need = volume / bandwidth
                    breach(need - (a1 - a0), need)
                    carried[s] += volume
                    first[s] = min(first[s], a0)
                    last[s] = max(last[s], a1)
                ran[ins.planes[pos]].append(
                    (a0, a1, kind, (ins.key, config), job)
                )
        for s, vol in enumerate(step_vol):
            breach(abs(carried[s] - vol), vol)
            if s and first[s] < float("inf"):
                breach(last[s - 1] - first[s], vol / bandwidth)
    for plane, leases in held.items():
        leases.sort()
        for (a0, a1, j), (b0, b1, k) in zip(leases, leases[1:]):
            if j != k:
                breach(a1 - b0, min(a1 - a0, b1 - b0))
    for plane, acts in ran.items():
        acts.sort(key=lambda a: (a[0], a[1]))
        installed = None
        busy_until = float("-inf")
        for a0, a1, kind, config, _job in acts:
            breach(busy_until - a0, a1 - a0)
            busy_until = max(busy_until, a1)
            if kind == "recfg":
                installed = config
            elif installed != config:
                return 1.0
    return worst
