"""SWOT-JAX: reconfiguration-communication overlap for collective
communication in optical networks, as a production JAX framework.

See README.md; public entry points:
  repro.core          -- the paper's contribution (scheduler/shim/...)
  repro.models.lm     -- build_model(cfg, ctx) for the 10-arch zoo
  repro.configs       -- registry.get_config / smoke_config
  repro.launch        -- mesh / dryrun / train / serve drivers
"""

import os
from pathlib import Path

# The checkout root: this file is <checkout>/src/repro/__init__.py.
_CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this before their first compile; importing the
    library never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    has already read it and nothing else is set.  Otherwise the cache
    lives at ``<checkout>/.jax_cache``: a fixed path, so that each run
    finds what earlier runs compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
