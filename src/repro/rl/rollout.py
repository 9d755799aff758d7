"""Autoregressive rollout with a decode cache: prefill + decode loop.

Used by the end-to-end examples and by the PlexRL ``generate`` service
primitive. Sampling is temperature-based with greedy as temperature->0;
returns behavior logprobs for importance-sampled objectives: each position's
is the log-probability of the token it emits, which after a row's
end-of-sequence token is ``eos_id`` and not the token drawn.

The decode cache is whatever the model family keeps: a KV cache for
attention, a conv window and SSM state for mamba2. With ``counters`` each
rollout adds ``generate.state_bytes`` (the bytes of that cache, read from
its shapes) and ``generate.decode_steps`` (the positions decoded).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.models.layers import Ctx
from repro.models.registry import Model


@dataclasses.dataclass(frozen=True)
class RolloutConfig:
    max_new_tokens: int = 32
    temperature: float = 1.0
    greedy: bool = False
    eos_id: int = 2


def _pad_cache(cache, extra: int):
    """Grow self-attn cache seq dims by `extra` slots (zero-filled)."""
    out = {}
    for k, v in cache.items():
        if k in ("k", "v", "attn_k", "attn_v") and hasattr(v, "ndim") and v.ndim >= 4:
            ax = v.ndim - 3
            pad = [(0, 0)] * v.ndim
            pad[ax] = (0, extra)
            out[k] = jnp.pad(v, pad)
        else:
            out[k] = v
    return out


def rollout(model: Model, params, prompt_tokens, rng,
            cfg: RolloutConfig = RolloutConfig(),
            ctx: Optional[Ctx] = None,
            extra_inputs: Optional[Dict[str, Any]] = None,
            counters: Optional[telemetry.Counters] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Generate completions. prompt_tokens: (B, P) int32.

    Returns (completions (B, N), logprobs (B, N), done_mask (B, N)).
    """
    batch = {"tokens": prompt_tokens, **(extra_inputs or {})}
    with telemetry.span("generate.prefill"):
        last_logits, _, cache = model.forward(params, batch, ctx,
                                              return_cache=True)
        last_logits = last_logits[:, -1]
        cache = _pad_cache(cache, cfg.max_new_tokens)
    if counters is not None:
        counters.add("generate.state_bytes", sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)))
        counters.add("generate.decode_steps", cfg.max_new_tokens)

    def sample(logits, key):
        if cfg.greedy or cfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(key, logits / cfg.temperature, axis=-1)

    def step(carry, key):
        logits, cache, alive = carry
        logits = logits.astype(jnp.float32)
        tok = jnp.where(alive, sample(logits, key), cfg.eos_id)
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                   tok[:, None], axis=-1)[:, 0]
        new_logits, new_cache = model.decode_step(params, cache,
                                                  {"tokens": tok[:, None]}, ctx)
        alive = alive & (tok != cfg.eos_id)
        return (new_logits[:, -1], new_cache, alive), (tok, logp, alive)

    with telemetry.span("generate.decode"):
        keys = jax.random.split(rng, cfg.max_new_tokens)
        b = prompt_tokens.shape[0]
        init = (last_logits, cache, jnp.ones((b,), bool))
        _, (toks, logps, alive) = jax.lax.scan(step, init, keys)
        return toks.T, logps.T, alive.T
