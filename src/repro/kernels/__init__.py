"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel: ``<name>.py`` (pl.pallas_call + explicit BlockSpec VMEM
tiling), a jit'd wrapper in ``ops.py``, and a pure-jnp oracle in
``ref.py``; all validated in interpret mode on CPU (TPU is the target).
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
