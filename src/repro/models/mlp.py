"""Dense feed-forward blocks: SwiGLU (LLaMA), GELU (GPT/HuBERT),
squared-ReLU (Nemotron/Minitron)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.modelspec import ModelSpec
from .common import KeyGen, ModelContext, activation, dense_init, rms_norm


def init_mlp(spec: ModelSpec, keys: KeyGen, dtype, d_ff: int | None = None
             ) -> dict:
    d = spec.d_model
    ff = d_ff if d_ff is not None else spec.d_ff
    p = {"norm": jnp.ones((d,), dtype),
         "w_up": dense_init(keys(), (d, ff), dtype),
         "w_down": dense_init(keys(), (ff, d), dtype)}
    if spec.act == "swiglu":
        p["w_gate"] = dense_init(keys(), (d, ff), dtype)
    return p


def mlp_axes(spec: ModelSpec) -> dict:
    axes = {"norm": ("embed_vec",), "w_up": ("embed", "mlp"),
            "w_down": ("mlp", "embed")}
    if spec.act == "swiglu":
        axes["w_gate"] = ("embed", "mlp")
    return axes


def mlp_block(spec: ModelSpec, ctx: ModelContext, params: dict,
              x: jax.Array, *, norm: bool = True) -> jax.Array:
    act = activation(spec.act)
    h = rms_norm(x, params["norm"]) if norm else x
    up = h @ params["w_up"]
    if spec.act == "swiglu":
        up = act(h @ params["w_gate"]) * up
    else:
        up = act(up)
    up = ctx.shard(up, "batch", "seq", "act_mlp")
    y = up @ params["w_down"]
    if ctx.tp_axis is not None:
        # column-sharded w_up/w_gate, row-sharded w_down: the partial
        # products all-reduce here — the layer pair's second collective
        with jax.named_scope("tp_psum"):
            y = jax.lax.psum(y, ctx.tp_axis)
    return ctx.shard(y, "batch", "seq_res", "act_embed")
