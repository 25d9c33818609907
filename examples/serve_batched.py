"""Serve a small model with batched requests.

    PYTHONPATH=src python examples/serve_batched.py
"""

import jax

from repro import use_compile_cache
from repro.configs.registry import smoke_config
from repro.models.lm import build_model
from repro.obs import get_logger
from repro.serve.engine import Request, ServeEngine
from repro.sharding.rules import single_device_context

log = get_logger("serve_batched")


def main() -> None:
    use_compile_cache()
    ctx = single_device_context()
    cfg = smoke_config("qwen2_1_5b")
    model = build_model(cfg, ctx)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_len=128)

    requests = [
        Request(prompt=[12, 45, 7, 99], max_new_tokens=12),
        Request(prompt=[3, 14, 15, 92, 65], max_new_tokens=8),
        Request(prompt=[42], max_new_tokens=16),
        Request(prompt=[8, 8, 8], max_new_tokens=10),
    ]
    completions = engine.generate(requests)
    for i, c in enumerate(completions):
        log.info(f"request {i}: prompt={c.prompt} -> tokens={c.tokens}")


if __name__ == "__main__":
    main()
