"""Topology Bypassing: relay routes over already-installed circuits.

The paper's third latency-hiding technique (alongside Heterogeneous
Message Splitting and Asynchronous Overlapping): when a step's pairing is
not installed on any plane, traffic can still flow as a *relay* over
circuits that ARE installed -- node ``x`` forwards its chunk to an
intermediate node over one installed circuit, which forwards it onward
over another, until the composition of the traversed permutations equals
the step's pairing.  No reconfiguration latency is paid; the price is
relay bandwidth: every hop carries the full chunk, so an ``h``-hop relay
delivers at ``bandwidth / h`` while consuming link capacity on each hop's
plane (the store-and-forward serialization the executor models).

Two enumeration flavors:

* **Self-composition** (`relay_depth_table`) -- an ``h``-hop walk over a
  SINGLE plane's installed circuit: ``x -> P[x] -> P^2[x] -> ...``; legal
  when ``P^h`` equals the step pairing.  This is the rotation-algebra
  case (ring / pairwise all-to-all: ``rot(a)^h = rot(h*a mod n)``) and
  the one the greedy scheduler enumerates, because a single plane's
  relay maps onto the water-filling machinery as a server with effective
  bandwidth ``bw / h``.
* **Cross-plane routes** (`enumerate_relay_routes`) -- BFS over
  compositions of DIFFERENT planes' installed circuits, returning hop
  plane tuples.  The executor/validator accept these general routes
  (P4); they are exposed for analyses and tests even though the greedy
  restricts itself to self-composition candidates.

Permutation convention: ``perm[x]`` is the node ``x`` sends to, and a
route's hops apply in forward data order, so a route ``(j0, j1)`` with
installed permutations ``p0, p1`` realizes ``x -> p1[p0[x]]``.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.core.patterns import Pattern


def config_perms(pattern: Pattern) -> dict[int, tuple[int, ...]]:
    """Config id -> node pairing, from the pattern's steps.

    ``Pattern.validate`` guarantees a config id maps to one permutation;
    config ids never mentioned by a step have no known pairing (and thus
    cannot participate in a relay composition).
    """
    perms: dict[int, tuple[int, ...]] = {}
    for step in pattern.steps:
        perms.setdefault(step.config, step.perm)
    return perms


def compose(first: tuple[int, ...], then: tuple[int, ...]) -> tuple[int, ...]:
    """Apply ``first`` then ``then``: ``result[x] = then[first[x]]``."""
    return tuple(then[y] for y in first)


def self_relay_depth(
    perm: tuple[int, ...], target: tuple[int, ...], max_depth: int
) -> int:
    """Minimal ``h`` in ``[2, max_depth]`` with ``perm^h == target``.

    Returns 0 when no such depth exists.  ``h = 1`` (the installed
    pairing already matches) is deliberately excluded: that is a direct
    transmission, not a bypass.
    """
    cur = perm
    for h in range(2, max_depth + 1):
        cur = compose(cur, perm)
        if cur == target:
            return h
    return 0


def relay_depth_table(pattern: Pattern, max_depth: int) -> np.ndarray:
    """``(C, C)`` table of minimal self-relay depths between config ids.

    Entry ``[a, c]`` is the minimal ``h`` in ``[2, max_depth]`` such that
    ``perm_a`` composed with itself ``h`` times equals ``perm_c``, or 0
    when no bypass exists (including unknown config ids).  ``C`` is
    ``max config id + 1`` over the pattern; ``max_depth < 2`` yields an
    all-zero table (bypassing disabled).
    """
    perms = config_perms(pattern)
    c_max = max(perms) + 1 if perms else 0
    table = np.zeros((c_max, c_max), dtype=np.int64)
    if max_depth < 2:
        return table
    for a, pa in perms.items():
        for c, pc in perms.items():
            table[a, c] = self_relay_depth(pa, pc, max_depth)
    return table


def relay_depth_tables(
    patterns: Sequence[Pattern], max_depth: int
) -> list[np.ndarray]:
    """`relay_depth_table` of each pattern, built once per distinct mapping.

    A table depends only on the pattern's config id -> pairing mapping, so
    patterns that share one (a grid's message sizes of one algorithm and
    node count) share one table: the same array object is returned for
    each of them.  Patterns are grouped first by identity, then by the
    mapping's content, in config-id order.
    """
    by_object: dict[int, np.ndarray] = {}
    by_mapping: dict[tuple, np.ndarray] = {}
    tables = []
    for pattern in patterns:
        table = by_object.get(id(pattern))
        if table is None:
            key = tuple(sorted(config_perms(pattern).items()))
            table = by_mapping.get(key)
            if table is None:
                table = by_mapping[key] = relay_depth_table(
                    pattern, max_depth
                )
            by_object[id(pattern)] = table
        tables.append(table)
    return tables


def enumerate_relay_routes(
    pattern: Pattern,
    step_config: int,
    installed: "list[int | None] | tuple[int | None, ...]",
    max_hops: int = 2,
    max_routes: int = 16,
) -> list[tuple[int, ...]]:
    """Plane-id routes whose installed circuits compose to ``step_config``.

    BFS over hop sequences of length ``2..max_hops`` (shorter routes
    first, then lexicographic plane order), pruning states whose reached
    permutation repeats at the same or shorter depth.  Planes whose
    installed config id has no known pairing are skipped.  Returns at
    most ``max_routes`` routes.
    """
    perms = config_perms(pattern)
    if step_config not in perms:
        raise ValueError(f"config {step_config} has no known pairing")
    target = perms[step_config]
    hop_perms = [
        (j, perms[c])
        for j, c in enumerate(installed)
        if c is not None and c in perms
    ]
    routes: list[tuple[int, ...]] = []
    # frontier: (route planes, reached permutation)
    frontier: list[tuple[tuple[int, ...], tuple[int, ...]]] = [
        ((j,), p) for j, p in hop_perms
    ]
    seen_depth: dict[tuple[tuple[int, ...], int], bool] = {}
    for depth in range(2, max_hops + 1):
        nxt: list[tuple[tuple[int, ...], tuple[int, ...]]] = []
        for planes, reached in frontier:
            for j, p in hop_perms:
                ext = compose(reached, p)
                route = planes + (j,)
                if ext == target:
                    routes.append(route)
                    if len(routes) >= max_routes:
                        return routes
                    continue
                key = (ext, depth)
                if key not in seen_depth:
                    seen_depth[key] = True
                    nxt.append((route, ext))
        frontier = nxt
    return routes
