"""Token sampling: greedy / temperature / top-k / top-p.

Two entry points:

  ``sample``       — one SamplingConfig for the whole batch (Python-level
                     branching on the config; used by the speculative and
                     beam decoders and as the semantics oracle).
  ``sample_slots`` — per-row sampling parameters as device arrays, with
                     no Python-level branching, so a single jitted call can
                     sample every engine slot in one shot even when
                     requests mix greedy and stochastic configs.  Row
                     semantics match ``sample`` exactly (temperature <= 0
                     means greedy).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 0.0  # 0 => greedy
    top_k: int = 0  # 0 => disabled
    top_p: float = 1.0


def sample(logits: jax.Array, rng: jax.Array,
           cfg: SamplingConfig) -> jax.Array:
    """logits: (B, V) -> (B,) int32 token ids."""
    if cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / cfg.temperature
    if cfg.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -cfg.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if cfg.top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < cfg.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def sample_slots(logits: jax.Array, keys: jax.Array, temperature: jax.Array,
                 top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Batched per-slot sampling, one independent config per row.

    logits: (B, V); keys: (B,) PRNG keys (one stream per slot);
    temperature/top_p: (B,) f32; top_k: (B,) i32 (0 disables).
    Returns (B,) int32.  Rows with temperature <= 0 are greedy argmax —
    identical to ``sample`` with the same per-row config.

    The temperature divide, both vocabulary sorts and the draw run inside
    one ``lax.cond`` taken on the device only when some row samples, so an
    all-greedy batch costs one argmax over (B, V).
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.lax.cond(
        jnp.any(temperature > 0.0),
        lambda: _filtered_draw(logits, keys, temperature, top_k, top_p,
                               greedy),
        lambda: greedy)


def _filtered_draw(logits, keys, temperature, top_k, top_p, greedy):
    """Temperature, top-k, top-p and the categorical draw for every row;
    greedy rows keep ``greedy``."""
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-8)[:, None]
    # top-k: kth-largest threshold per row (k clipped into range; rows with
    # top_k <= 0 keep everything)
    desc = jnp.sort(lf, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_k - 1, 0, v - 1)
    kth = jnp.take_along_axis(desc, k_idx[:, None], axis=-1)
    lf = jnp.where((top_k[:, None] > 0) & (lf < kth), -jnp.inf, lf)
    # top-p (nucleus) over the top-k-filtered distribution
    desc = jnp.sort(lf, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(desc, cutoff_idx[:, None], axis=-1)
    lf = jnp.where((top_p[:, None] < 1.0) & (lf < cutoff), -jnp.inf, lf)

    stochastic = jax.vmap(jax.random.categorical)(keys, lf).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, stochastic)
