"""The collectives' step lists, restated from their definitions.

Each collective on ``n`` nodes is a chain of steps; a step installs one
pairing (a config id) and moves a volume in bytes per node pair.  Pairwise
all-to-all: ``n - 1`` rotations by ``k``, each ``size / n``.  Rabenseifner
all-reduce: recursive halving (step ``t`` pairs ``i xor 2^(t-1)`` and moves
``size / 2^t``) then the mirror-image recursive doubling.  Recursive-
doubling all-gather: the second half of Rabenseifner.
"""

from __future__ import annotations

import numpy as np


def _log2(n: int) -> int:
    log = n.bit_length() - 1
    if 1 << log != n:
        raise ValueError(f"needs a power-of-two node count, got {n}")
    return log


def steps_of(name: str, n: int, size: float) -> tuple[np.ndarray, np.ndarray]:
    """(config id per step, bytes per step) of collective ``name``."""
    if name == "pairwise_alltoall":
        cfg = np.arange(n - 1)
        vol = np.full(n - 1, size / n)
    elif name == "rabenseifner_allreduce":
        t = np.arange(1, _log2(n) + 1)
        t = np.concatenate([t, t[::-1]])
        cfg, vol = t - 1, size / 2.0**t
    elif name == "all_gather":
        t = np.arange(_log2(n), 0, -1)
        cfg, vol = t - 1, size / 2.0**t
    else:
        raise ValueError(f"no reference step list for {name!r}")
    return cfg.astype(np.int64), vol.astype(np.float64)
