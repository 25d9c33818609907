"""Batched serving engine: prefill + greedy decode over request batches.

Slot-based batching: requests are padded into a fixed-size batch, the
prompt is prefetched in one prefill call, and decoding proceeds greedily
until max tokens.  The SWOT shim can be attached to account for the
optical cost of serving-time collectives (TP all-gathers during decode).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import Model


@dataclasses.dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 16


@dataclasses.dataclass
class Completion:
    prompt: list[int]
    tokens: list[int]


class ServeEngine:
    def __init__(
        self, model: Model, params, max_len: int = 256, recorder=None
    ):
        self.model = model
        self.params = params
        self.max_len = max_len
        # Optional repro.trace.TraceRecorder: generate() records the
        # prefill's collectives, then each decode tick's, with a step
        # boundary per engine step (prefill = one step, decode tick =
        # one step) -- the serving-side analogue of the Trainer hook.
        self.recorder = recorder
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)

    def _record_step(self, kind: str, batch_size: int, seq_len: int) -> None:
        """Feed the recorder one engine step's Phase-1 profile."""
        if self.recorder is None:
            return
        from repro.configs.base import ShapeCell
        from repro.core.planner import profile_serve_step

        cell = ShapeCell(
            name=f"live_{kind}", kind=kind,
            seq_len=max(seq_len, 1), global_batch=max(batch_size, 1),
        )
        for req in profile_serve_step(self.model.cfg, self.model.ctx, cell):
            self.recorder.record(req, phase=kind)
        self.recorder.step_boundary()

    def _pad_batch(self, requests: list[Request]) -> tuple[jax.Array, int]:
        max_prompt = max(len(r.prompt) for r in requests)
        tokens = np.zeros((len(requests), max_prompt), np.int32)
        for i, r in enumerate(requests):
            # Left-pad with token 1 so every prompt ends at the same
            # position (keeps the prefill cache rectangular).
            tokens[i, max_prompt - len(r.prompt) :] = r.prompt
            tokens[i, : max_prompt - len(r.prompt)] = 1
        return jnp.asarray(tokens), max_prompt

    def generate(self, requests: list[Request]) -> list[Completion]:
        cfg = self.model.cfg
        tokens, prompt_len = self._pad_batch(requests)
        batch = {"tokens": tokens}
        if cfg.n_image_patches and cfg.family in ("vlm", "moe"):
            batch["image_embeds"] = jnp.zeros(
                (tokens.shape[0], cfg.n_image_patches, cfg.d_model),
                jnp.bfloat16,
            )
        if cfg.family == "audio":
            batch["encoder_frames"] = jnp.zeros(
                (tokens.shape[0], cfg.n_audio_frames, cfg.d_model),
                jnp.bfloat16,
            )
        with jax.set_mesh(self.model.ctx.mesh):
            logits, cache = self._prefill(self.params, batch)
            self._record_step("prefill", tokens.shape[0], prompt_len)
            cache = self._grow(cache, tokens.shape[0])
            max_new = max(r.max_new_tokens for r in requests)
            outs = []
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            for _ in range(max_new):
                outs.append(np.asarray(tok)[:, 0])
                logits, cache = self._decode(self.params, cache, tok)
                self._record_step(
                    "decode", tokens.shape[0], prompt_len + len(outs)
                )
                tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        columns = np.stack(outs, axis=1)  # (B, max_new)
        return [
            Completion(
                prompt=list(r.prompt),
                tokens=[int(t) for t in columns[i, : r.max_new_tokens]],
            )
            for i, r in enumerate(requests)
        ]

    def _grow(self, cache, batch_size: int):
        """Pad prefill-length KV caches to max_len capacity."""
        specs = self.model.cache_specs(batch_size, self.max_len)
        grown = {}
        for name, value in cache.items():
            spec = specs[name]
            if (
                hasattr(spec, "shape")
                and value.ndim >= 3
                and value.shape != spec.shape
            ):
                pads = [
                    (0, max(0, t - c))
                    for c, t in zip(value.shape, spec.shape)
                ]
                grown[name] = jnp.pad(value, pads)
            else:
                grown[name] = value
        return grown
