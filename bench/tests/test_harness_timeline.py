"""The rebuilt fabric timeline (`reference.timeline`) on hand-made plans:
legal timelines read 0; a plane used by two jobs at once, a plan that
ignores an earlier job's tail, a config read across namespaces, a lease
granted twice and a step left short each read a breach."""

import harness  # noqa: F401  (puts the benchmark on sys.path)
import pytest
from reference.timeline import Install, violation

BW = 1e9  # bytes/s
T_RECFG = 1e-3
A2A = ("pairwise_alltoall", 8)
AR = ("rabenseifner_allreduce", 8)


def install(job, at, planes, acts, key=A2A, t0=None, base=0):
    return Install(job=job, at=at, t0=at if t0 is None else t0,
                   planes=tuple(planes), base_step=base, key=key,
                   activities=tuple(acts))


def recfg(pos, step, start, config):
    return (pos, "recfg", step, start, start + T_RECFG, config, 0.0)


def xmit(pos, step, start, config, volume):
    return (pos, "xmit", step, start, start + volume / BW, config, volume)


# Job 0: two steps (configs 0, 1) of 1 MB each on plane 0.
STEPS = {0: ([0, 1], [1e6, 1e6]), 1: ([0], [2e6])}
JOB0 = [recfg(0, 0, 0.0, 0), xmit(0, 0, 1e-3, 0, 1e6),
        recfg(0, 1, 2e-3, 1), xmit(0, 1, 3e-3, 1, 1e6)]


def check(installs, finish, steps=STEPS):
    return violation(installs, finish, steps, BW, T_RECFG)


def test_two_jobs_one_after_the_other_are_legal():
    job1 = [recfg(0, 0, 0.0, 0), xmit(0, 0, 1e-3, 0, 2e6)]
    installs = [install(0, 0.0, [0], JOB0),
                install(1, 4e-3, [0], job1, key=AR)]
    assert check(installs, {0: 4e-3, 1: 7e-3}) == 0.0


def test_an_earlier_jobs_tail_ignored_is_a_breach():
    # Job 0 carries step 0 on plane 1 and reconfigures plane 0 ahead
    # (1.5-2.5 ms); at the 2 ms boundary it shrinks to plane 1 and plane 0
    # goes to job 1 with that reconfiguration still running.
    first = install(0, 0.0, [0, 1], [
        recfg(1, 0, 0.0, 0), xmit(1, 0, 1e-3, 0, 1e6), recfg(0, 1, 1.5e-3, 1),
    ])
    second = install(0, 2e-3, [1], [recfg(0, 0, 0.0, 1),
                                    xmit(0, 0, 1e-3, 1, 1e6)], base=1)
    job1 = [recfg(0, 0, 0.0, 0), xmit(0, 0, 1e-3, 0, 2e6)]
    late = install(1, 2e-3, [0], job1, key=AR, t0=2.5e-3)
    early = install(1, 2e-3, [0], job1, key=AR)
    assert check([first, second, late], {0: 4e-3, 1: 5.5e-3}) < 1e-12
    assert check([first, second, early], {0: 4e-3, 1: 5e-3}) == (
        pytest.approx(0.5)
    )


def test_a_config_of_another_namespace_is_a_breach():
    # Job 1 (another algorithm) transmits on config 0 that job 0 left
    # installed: equal ids, different permutations.
    job1 = [xmit(0, 0, 0.0, 0, 2e6)]
    installs = [install(0, 0.0, [0], JOB0[:2]),
                install(1, 2e-3, [0], job1, key=AR)]
    steps = {0: ([0], [1e6]), 1: ([0], [2e6])}
    assert check(installs, {0: 2e-3, 1: 4e-3}, steps) == 1.0
    same = install(1, 2e-3, [0], job1, key=A2A)
    assert check([installs[0], same], {0: 2e-3, 1: 4e-3}, steps) == 0.0


def test_a_plane_used_by_two_jobs_at_once_is_a_breach():
    job1 = [recfg(0, 0, 0.0, 0), xmit(0, 0, 1e-3, 0, 2e6)]
    installs = [install(0, 0.0, [0], JOB0),
                install(1, 1e-3, [0], job1, key=AR)]
    assert check(installs, {0: 4e-3, 1: 4e-3}) > 0.5


def test_a_lease_granted_twice_is_a_breach_even_while_idle():
    # Job 1 uses plane 1 only, but its lease also names plane 0, which
    # job 0 holds until 4 ms.
    job1 = [recfg(0, 0, 0.0, 0), xmit(0, 0, 1e-3, 0, 2e6)]
    installs = [install(0, 0.0, [0], JOB0),
                install(1, 1e-3, [1, 0], job1, key=AR)]
    assert check(installs, {0: 4e-3, 1: 4e-3}) > 0.5


def test_superseded_activities_never_ran():
    # The first plan's step-1 activities start after the re-plan at 2 ms
    # and are dropped; the second plan carries step 1 on plane 1.
    second = install(0, 2e-3, [1], [recfg(0, 0, 0.0, 1),
                                    xmit(0, 0, 1e-3, 1, 1e6)], base=1)
    first = install(0, 0.0, [0], [*JOB0[:2], recfg(0, 1, 2.5e-3, 1),
                                  xmit(0, 1, 3.5e-3, 1, 1e6)])
    assert check([first, second], {0: 4e-3}) == 0.0


@pytest.mark.parametrize("short", [0.5, 0.9])
def test_a_step_left_short_is_a_breach(short):
    acts = [recfg(0, 0, 0.0, 0), xmit(0, 0, 1e-3, 0, short * 1e6),
            recfg(0, 1, 2e-3, 1), xmit(0, 1, 3e-3, 1, 1e6)]
    assert check([install(0, 0.0, [0], acts)], {0: 4e-3}) == pytest.approx(
        1.0 - short
    )


def test_a_job_that_never_finished_is_a_breach():
    assert check([install(0, 0.0, [0], JOB0)], {}) == 1.0
