"""Shared building blocks: model context, norms, RoPE, activations, inits."""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..core.modelspec import ModelSpec
from ..sharding import ShardingPolicy, constrain as _constrain, get_policy


@dataclass(frozen=True)
class ModelContext:
    """Everything a layer needs besides its parameters."""

    spec: ModelSpec
    mesh: Mesh | None = None
    policy: ShardingPolicy = field(default_factory=lambda: get_policy("inference_tp"))
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    #: attention implementation: auto | direct | flash | pallas | gather.
    #: "auto" picks direct/flash for dense caches and, for the paged and
    #: token-packed paths, the Pallas kernels on TPU and the gather oracle
    #: elsewhere (:meth:`paged_kernel`); an explicit value wins.
    attn_impl: str = "auto"
    flash_block_q: int = 512
    flash_block_kv: int = 1024
    #: MoE implementation: dense (einsum dispatch) | shardmap (explicit A2A)
    moe_impl: str = "auto"
    moe_capacity_factor: float = 1.25
    #: §Perf knob: partition EP-replicated tokens across ranks pre-routing
    #: (removes m_sz-fold redundant expert compute + dispatch traffic).
    moe_partition_tokens: bool = False
    #: §Perf knob: triangular block schedule for causal flash (skips fully
    #: masked kv blocks instead of computing + masking them).
    flash_causal_skip: bool = False
    #: §Perf knob: int8 KV cache (per-token/head scales) — halves the
    #: decode stream at a small (lossy) accuracy cost (paper Table V).
    kv_quant: bool = False
    #: §Perf knob: decode keeps the whole stacked cache as the layer-scan
    #: carry (in-place token insert) instead of streaming it through xs/ys,
    #: removing the per-layer slice-out/slice-back round trips.
    decode_carry_cache: bool = False
    #: KV-cache layout: "dense" reserves (B, max_seq) per layer; "paged"
    #: keeps a flat page pool + page-table indirection so capacity scales
    #: with tokens used, not slots reserved (paper §V capacity lever).
    cache_layout: str = "dense"
    #: tokens per KV page for the paged layout (internal fragmentation is
    #: bounded by one page per request)
    kv_page_size: int = 16
    #: named mesh axis this context runs *inside* (a ``shard_map`` worker
    #: with Megatron-style column/row-sharded weights): attention's output
    #: projection and the MLP down projection each ``psum`` their partial
    #: results over it — exactly one all-reduce per column/row pair.  None
    #: outside shard_map (single device, or GSPMD via ``mesh``).
    tp_axis: str | None = None

    def shard(self, x: jax.Array, *logical_axes: str | None) -> jax.Array:
        if self.mesh is None:
            return x
        return _constrain(x, logical_axes, self.policy.rules, self.mesh)

    def with_(self, **kw) -> "ModelContext":
        return replace(self, **kw)

    def paged_kernel(self) -> tuple[str, bool]:
        """``(impl, interpret)`` of the paged decode and ragged kernels.

        ``attn_impl="auto"`` resolves, when the program is traced, from the
        devices it runs on (the mesh's, else the default backend): the
        Pallas kernels on TPU, the gather oracle anywhere else.  "pallas"
        and "gather" are taken as given.  Pallas compiles for TPU and runs
        in interpret mode only where there is none."""
        platform = (self.mesh.devices.flat[0].platform if self.mesh is not None
                    else jax.default_backend())
        impl = self.attn_impl
        if impl == "auto":
            impl = "pallas" if platform == "tpu" else "gather"
        if impl == "pallas":
            return "pallas", platform != "tpu"
        return "gather", False


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * scale.astype(dt)


def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    if name == "swiglu":  # the gate nonlinearity of SwiGLU
        return jax.nn.silu
    if name == "gelu":
        return partial(jax.nn.gelu, approximate=True)
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


def rope_freqs(d_head: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float64)
                            / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) rotated pairwise; positions: broadcastable to
    x.shape[:-2] ending in S."""
    d = x.shape[-1]
    inv = jnp.asarray(rope_freqs(d, theta), dtype=jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, D/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., None, :]  # broadcast over heads
    cos = cos[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis: int = 0) -> jax.Array:
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * 0.02).astype(dtype)


class KeyGen:
    """Splits a PRNG key on demand: ``k = keys()``."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub


def big_neg(dtype) -> jax.Array:
    return jnp.asarray(jnp.finfo(jnp.float32).min / 2, dtype=dtype)
