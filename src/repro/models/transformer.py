"""Decoder stack: period-grouped scan over layers.

Layers are grouped by their *class* (mixer kind x MoE-ness).  The stack finds
the smallest period ``p`` such that class[i] == class[i mod p] (p=1 for
uniform models, p=8 for Jamba's [7 mamba : 1 attn] blocks with MoE every
other layer), stacks parameters per period position over the ``repeats``
axis, and runs ``lax.scan`` over repeats with the ``p`` positions unrolled
inside.  This keeps the compiled HLO O(p) instead of O(n_layers) — essential
for compiling 512-way SPMD programs quickly — while my HLO cost analyzer
recovers true totals from the loop trip counts.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..core.modelspec import ModelSpec
from .attention import (AttnCache, PagedAttnCache, attention_axes,
                        attention_block, init_attention, init_attn_cache,
                        init_paged_attn_cache)
from .common import KeyGen, ModelContext
from .mlp import init_mlp, mlp_axes, mlp_block
from .moe import init_moe, moe_axes, moe_block
from .ssm import (MambaCache, RWKVCache, init_mamba, init_mamba_cache,
                  init_rwkv6, init_rwkv_cache, mamba_axes, rwkv6_axes,
                  mamba_block, rwkv6_block)


@dataclass(frozen=True)
class LayerClass:
    kind: str  # attn | mamba | rwkv6
    is_moe: bool

    @property
    def key(self) -> str:
        return f"{self.kind}{'_moe' if self.is_moe else ''}"


def layer_classes(spec: ModelSpec) -> list[LayerClass]:
    kinds = spec.layer_kinds()
    out = []
    for i, k in enumerate(kinds):
        if k == "ssm":
            kind = "rwkv6" if (spec.ssm and spec.ssm.kind == "rwkv6") else "mamba"
        else:
            kind = "attn"
        is_moe = spec.moe is not None and spec.moe.is_moe_layer(i)
        out.append(LayerClass(kind, is_moe))
    return out


def stack_period(spec: ModelSpec) -> tuple[int, int]:
    """-> (period, repeats): smallest p with class[i] == class[i mod p]."""
    classes = layer_classes(spec)
    n = len(classes)
    for p in range(1, n + 1):
        if n % p:
            continue
        if all(classes[i] == classes[i % p] for i in range(n)):
            return p, n // p
    return n, 1


# ---------------------------------------------------------------------------
# Per-position init / axes / apply
# ---------------------------------------------------------------------------

def _init_one(spec: ModelSpec, cls: LayerClass, keys: KeyGen, dtype,
              n_shards: int) -> dict:
    p: dict[str, Any] = {}
    if cls.kind == "attn":
        p["mixer"] = init_attention(spec, keys, dtype)
    elif cls.kind == "mamba":
        p["mixer"] = init_mamba(spec, keys, dtype)
    else:
        p["mixer"] = init_rwkv6(spec, keys, dtype)
    if cls.kind != "rwkv6":  # rwkv's channel mix is its FFN
        if cls.is_moe:
            p["ffn"] = init_moe(spec, keys, dtype, n_shards)
        elif spec.d_ff > 0:
            p["ffn"] = init_mlp(spec, keys, dtype)
    return p


def _axes_one(spec: ModelSpec, cls: LayerClass) -> dict:
    a: dict[str, Any] = {}
    if cls.kind == "attn":
        a["mixer"] = attention_axes(spec)
    elif cls.kind == "mamba":
        a["mixer"] = mamba_axes(spec)
    else:
        a["mixer"] = rwkv6_axes(spec)
    if cls.kind != "rwkv6":
        if cls.is_moe:
            a["ffn"] = moe_axes(spec)
        elif spec.d_ff > 0:
            a["ffn"] = mlp_axes(spec)
    return a


def _apply_one(spec: ModelSpec, ctx: ModelContext, cls: LayerClass,
               params: dict, x, positions, cache, lengths,
               page_table=None, packed=None):
    if cls.kind == "attn":
        with jax.named_scope("attn"):
            y, new_cache = attention_block(spec, ctx, params["mixer"], x,
                                           positions, cache, lengths,
                                           page_table=page_table,
                                           packed=packed)
        x = x + y
    elif packed is not None:
        raise NotImplementedError(
            "the token-packed unified step supports attention-only "
            f"stacks; layer kind {cls.kind!r} carries sequential state")
    elif cls.kind == "mamba":
        y, new_cache = mamba_block(spec, ctx, params["mixer"], x, cache)
        x = x + y
    else:
        x, new_cache = rwkv6_block(spec, ctx, params["mixer"], x, cache)
    if "ffn" in params:
        if cls.is_moe:
            x = x + moe_block(spec, ctx, params["ffn"], x)
        else:
            with jax.named_scope("mlp"):
                x = x + mlp_block(spec, ctx, params["ffn"], x)
    x = ctx.shard(x, "batch", "seq_res", "act_embed")
    return x, new_cache


def _init_cache_one(spec: ModelSpec, cls: LayerClass, batch: int,
                    max_len: int, dtype, quantized: bool = False,
                    layout: str = "dense", page_size: int = 16,
                    n_pages: int | None = None):
    if cls.kind == "attn":
        if layout == "paged":
            return init_paged_attn_cache(spec, n_pages, page_size, dtype,
                                         quantized)
        return init_attn_cache(spec, batch, max_len, dtype, quantized)
    # SSM / conv states are constant-size per request: paging never applies
    if cls.kind == "mamba":
        return init_mamba_cache(spec, batch, dtype)
    return init_rwkv_cache(spec, batch, dtype)


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

def init_stack(spec: ModelSpec, keys: KeyGen, dtype, n_shards: int) -> dict:
    period, repeats = stack_period(spec)
    classes = layer_classes(spec)[:period]
    params: dict[str, Any] = {}
    for pos, cls in enumerate(classes):
        stacked = [_init_one(spec, cls, keys, dtype, n_shards)
                   for _ in range(repeats)]
        params[f"pos{pos}"] = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0), *stacked)
    return params


def stack_axes(spec: ModelSpec) -> dict:
    period, _ = stack_period(spec)
    classes = layer_classes(spec)[:period]
    axes: dict[str, Any] = {}
    for pos, cls in enumerate(classes):
        one = _axes_one(spec, cls)
        axes[f"pos{pos}"] = jax.tree.map(
            lambda a: ("layers",) + tuple(a), one,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x))
    return axes


def _cache_axes_one(spec: ModelSpec, cls: LayerClass,
                    quantized: bool = False, layout: str = "dense"):
    if cls.kind == "attn":
        if layout == "paged":
            # the page pool is indexed by page id, not request: only the
            # kv-head axis is meaningfully shardable (resident layout:
            # (P, Hkv, page_size, Dh))
            kv = ("layers", None, "act_kv_heads", None, None)
            sc = ("layers", None, "act_kv_heads", None) if quantized else None
            return PagedAttnCache(k=kv, v=kv, k_scale=sc, v_scale=sc)
        kv = ("layers", "batch", "kv_seq", "act_kv_heads", None)
        sc = ("layers", "batch", "kv_seq", "act_kv_heads") if quantized \
            else None
        return AttnCache(k=kv, v=kv, k_scale=sc, v_scale=sc)
    if cls.kind == "mamba":
        return MambaCache(conv=("layers", "batch", None, "act_ssm_inner"),
                          ssm=("layers", "batch", "act_ssm_inner", None))
    return RWKVCache(tm_shift=("layers", "batch", None, None),
                     cm_shift=("layers", "batch", None, None),
                     wkv=("layers", "batch", "ssm_heads", None, None))


def stack_cache_axes(spec: ModelSpec, quantized: bool = False,
                     layout: str = "dense") -> dict:
    period, _ = stack_period(spec)
    classes = layer_classes(spec)[:period]
    return {f"pos{pos}": _cache_axes_one(spec, cls, quantized, layout)
            for pos, cls in enumerate(classes)}


def init_stack_cache(spec: ModelSpec, batch: int, max_len: int, dtype,
                     quantized: bool = False, layout: str = "dense",
                     page_size: int = 16, n_pages: int | None = None):
    period, repeats = stack_period(spec)
    classes = layer_classes(spec)[:period]
    cache: dict[str, Any] = {}
    for pos, cls in enumerate(classes):
        one = _init_cache_one(spec, cls, batch, max_len, dtype, quantized,
                              layout=layout, page_size=page_size,
                              n_pages=n_pages)
        cache[f"pos{pos}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (repeats,) + x.shape), one)
    return cache


def apply_stack(spec: ModelSpec, ctx: ModelContext, params: dict,
                x: jax.Array, positions: jax.Array, cache=None,
                lengths=None, page_table=None, packed=None):
    """Run all layers.  cache is the stacked pytree from init_stack_cache
    (or None for a cache-free pass).  ``page_table`` is the shared
    (B, max_pages) indirection when the attention caches are paged;
    ``packed`` the shared :class:`~repro.models.attention.PackedSegs`
    segment table when x is a token-packed unified step."""
    period, repeats = stack_period(spec)
    classes = layer_classes(spec)[:period]
    with_cache = cache is not None

    def superblock(x, slice_):
        p_slice, c_slice = slice_
        new_c = {}
        for pos, cls in enumerate(classes):
            c_in = c_slice[f"pos{pos}"] if with_cache else None
            x, c_out = _apply_one(spec, ctx, cls, p_slice[f"pos{pos}"], x,
                                  positions, c_in, lengths,
                                  page_table=page_table, packed=packed)
            if with_cache:
                new_c[f"pos{pos}"] = c_out
        return x, (new_c if with_cache else None)

    body = superblock
    if ctx.policy.remat == "full":
        body = jax.checkpoint(superblock)

    if with_cache and x.shape[1] == 1 and ctx.decode_carry_cache:
        # §Perf: cache-as-carry decode.  The stacked cache rides the scan
        # carry; each iteration dynamic-slices its repeat, runs the layers,
        # and writes the slice back — XLA keeps loop-carried buffers in
        # place, so the per-layer ys copy of the whole cache disappears.
        def carry_body(carry, xs_):
            xc, cache_full = carry
            p_slice, r = xs_
            c_slice = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, r, 0,
                                                       keepdims=False),
                cache_full)
            xc, new_c = superblock(xc, (p_slice, c_slice))
            cache_full = jax.tree.map(
                lambda c, ns: jax.lax.dynamic_update_index_in_dim(
                    c, ns.astype(c.dtype), r, 0),
                cache_full, new_c)
            return (xc, cache_full), None

        (x, new_cache), _ = jax.lax.scan(
            carry_body, (x, cache), (params, jnp.arange(repeats)))
        return x, new_cache

    if with_cache:
        x, new_cache = jax.lax.scan(body, x, (params, cache))
    else:
        def no_cache_body(x, p_slice):
            y, _ = body(x, (p_slice, None))
            return y, None

        x, _ = jax.lax.scan(no_cache_body, x, params)
        new_cache = None
    return x, new_cache
