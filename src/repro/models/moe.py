"""Mixture-of-Experts block (paper §II-A, §IV-C).

Supports fine-grained routed experts (DeepSeek-MoE: 64 experts top-6),
always-on shared experts, and per-period MoE placement (Jamba: every other
layer).  Router: softmax -> top-k -> renormalize.

Two implementations:

  dense    : every expert computed for every token, combined by routing
             weights.  No token dropping — the correctness oracle, used on
             single devices and in smoke tests.  O(E/K) extra FLOPs.
  shardmap : production expert parallelism over the ``model`` mesh axis —
             tokens are sorted by destination shard, exchanged with
             ``lax.all_to_all`` (the paper's EP dispatch collective),
             scattered into per-expert buffers, processed by batched
             per-expert GEMMs, and combined through a reverse all-to-all.
             Fixed per-link capacity (``capacity_factor``) => static shapes;
             overflow tokens are dropped exactly like GShard/Switch.

Shared experts run as a dense MLP of width shared * d_ff_expert with plain
TP — they see every token, so there is nothing to route.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.modelspec import ModelSpec
from .common import KeyGen, ModelContext, activation, dense_init, rms_norm
from .mlp import init_mlp, mlp_axes, mlp_block


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def init_moe(spec: ModelSpec, keys: KeyGen, dtype, n_shards: int = 1) -> dict:
    m = spec.moe
    assert m is not None
    d, ff = spec.d_model, m.d_ff_expert
    e_pad = _round_up(m.num_experts, max(n_shards, 1))
    p = {
        "norm": jnp.ones((d,), dtype),
        "router": dense_init(keys(), (d, m.num_experts), dtype),
        "w_up": dense_init(keys(), (e_pad, d, ff), dtype),
        "w_down": dense_init(keys(), (e_pad, ff, d), dtype),
    }
    if spec.act == "swiglu":
        p["w_gate"] = dense_init(keys(), (e_pad, d, ff), dtype)
    if m.shared_experts:
        shared_spec = spec.scaled(d_ff=m.shared_experts * ff)
        p["shared"] = init_mlp(shared_spec, keys, dtype)
    return p


def moe_axes(spec: ModelSpec) -> dict:
    axes = {
        "norm": ("embed_vec",),
        "router": ("embed", None),
        "w_up": ("experts", "embed", "expert_mlp"),
        "w_down": ("experts", "expert_mlp", "embed"),
    }
    if spec.act == "swiglu":
        axes["w_gate"] = ("experts", "embed", "expert_mlp")
    if spec.moe and spec.moe.shared_experts:
        axes["shared"] = mlp_axes(spec)
    return axes


def _route(spec: ModelSpec, h: jax.Array, router_w: jax.Array):
    """h: (N, D) -> (weights (N,K), ids (N,K)); softmax->topk->renorm."""
    logits = h.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, spec.moe.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, ids


def _expert_ffn(spec: ModelSpec, params: dict, x: jax.Array) -> jax.Array:
    """Batched per-expert FFN: x (E, C, D) -> (E, C, D)."""
    from ..kernels import ops as kops
    act = activation(spec.act)
    up = kops.expert_gemm(x, params["w_up"])
    if spec.act == "swiglu":
        up = act(kops.expert_gemm(x, params["w_gate"])) * up
    else:
        up = act(up)
    return kops.expert_gemm(up, params["w_down"])


# ---------------------------------------------------------------------------
# Dense (no-drop oracle) implementation
# ---------------------------------------------------------------------------

def _moe_dense(spec: ModelSpec, ctx: ModelContext, params: dict,
               h: jax.Array) -> jax.Array:
    b, s, d = h.shape
    n = b * s
    hf = h.reshape(n, d)
    weights, ids = _route(spec, hf, params["router"])
    e_pad = params["w_up"].shape[0]
    # combine weights over all experts: (N, E)
    comb = jnp.zeros((n, e_pad), jnp.float32)
    comb = comb.at[jnp.arange(n)[:, None], ids].add(weights)
    outs = _expert_ffn(spec, params,
                       jnp.broadcast_to(hf, (e_pad, n, d)))  # (E, N, D)
    y = jnp.einsum("end,ne->nd", outs.astype(jnp.float32), comb)
    return y.reshape(b, s, d).astype(h.dtype)


# ---------------------------------------------------------------------------
# shard_map expert-parallel implementation
# ---------------------------------------------------------------------------

def _sorted_positions(dest: jax.Array, n_bins: int):
    """For each element, its arrival rank within its destination bin."""
    n = dest.shape[0]
    onehot = jax.nn.one_hot(dest, n_bins, dtype=jnp.int32)  # (N, M)
    pos = jnp.cumsum(onehot, axis=0) - 1  # rank within bin
    return jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]


def _moe_shardmap_body(spec: ModelSpec, e_local: int, c_send: int,
                       c_cap: int, m_sz: int, partition: bool, axis: str,
                       params: dict, h: jax.Array) -> jax.Array:
    """Per-shard body.  h: (B_loc, S, D) local tokens (replicated along the
    EP/model axis by the surrounding data-parallel sharding).

    ``partition=True`` (§Perf iteration, default on when divisible): each EP
    rank routes only its 1/m_sz slice of the local tokens, so dispatch
    payloads and expert GEMMs carry unique work; the outputs are re-gathered
    at the end.  Without it every rank routes the identical replicated set —
    m_sz-fold redundant compute and wire traffic.
    """
    b, s, d = h.shape
    n_full = b * s
    hf_full = h.reshape(n_full, d)
    if partition:
        rank = jax.lax.axis_index(axis)
        n = n_full // m_sz
        hf = jax.lax.dynamic_slice_in_dim(hf_full, rank * n, n, axis=0)
    else:
        n = n_full
        hf = hf_full
    weights, ids = _route(spec, hf, params["router"])  # (N,K)
    k = spec.moe.top_k

    flat_ids = ids.reshape(-1)  # (N*K,) global expert id
    flat_w = weights.reshape(-1).astype(jnp.float32)
    src = jnp.repeat(jnp.arange(n), k)  # source token per assignment
    dest = flat_ids // e_local  # destination shard
    pos = _sorted_positions(dest, m_sz)
    keep = pos < c_send

    # --- dispatch: (M, C_send, ...) send buffers ---------------------------
    def scatter(vals, fill=0):
        buf = jnp.full((m_sz, c_send) + vals.shape[1:], fill, vals.dtype)
        return buf.at[dest, pos].set(vals, mode="drop",
                                     unique_indices=True)

    send_tok = scatter(jnp.where(keep[:, None], hf[src], 0))
    send_eid = scatter(jnp.where(keep, flat_ids % e_local, e_local)
                       .astype(jnp.int32), fill=e_local)
    send_slot = scatter(jnp.where(keep, jnp.arange(n * k), -1)
                        .astype(jnp.int32), fill=-1)

    recv_tok = jax.lax.all_to_all(send_tok, axis, 0, 0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, axis, 0, 0, tiled=False)

    # --- local per-expert buffers ------------------------------------------
    r_tok = recv_tok.reshape(m_sz * c_send, d)
    r_eid = recv_eid.reshape(m_sz * c_send)
    epos = _sorted_positions(r_eid, e_local + 1)  # +1: invalid bin
    ekeep = (r_eid < e_local) & (epos < c_cap)
    ebuf = jnp.zeros((e_local + 1, c_cap, d), r_tok.dtype)
    ebuf = ebuf.at[jnp.where(ekeep, r_eid, e_local),
                   jnp.where(ekeep, epos, 0)].add(
        jnp.where(ekeep[:, None], r_tok, 0), mode="drop")

    eout = _expert_ffn(spec, params, ebuf[:e_local])  # (E_loc, C_cap, D)
    eout = jnp.concatenate(
        [eout, jnp.zeros((1, c_cap, d), eout.dtype)], axis=0)

    back = eout[jnp.where(ekeep, r_eid, e_local),
                jnp.where(ekeep, epos, 0)]  # (M*C_send, D)
    back = jnp.where(ekeep[:, None], back, 0).reshape(m_sz, c_send, d)

    # --- combine: reverse exchange + weighted scatter-add -------------------
    ret = jax.lax.all_to_all(back, axis, 0, 0, tiled=False)
    ret = ret.reshape(m_sz * c_send, d).astype(jnp.float32)
    slot = send_slot.reshape(m_sz * c_send)
    valid = slot >= 0
    slot_src = jnp.where(valid, slot // k, 0)
    w = jnp.where(valid, flat_w[jnp.where(valid, slot, 0)], 0.0)
    y = jnp.zeros((n, d), jnp.float32)
    y = y.at[slot_src].add(ret * w[:, None], mode="drop")
    if partition:
        y = jax.lax.all_gather(y, axis, axis=0, tiled=True)  # (n_full, d)
    return y.reshape(b, s, d).astype(h.dtype)


def _moe_shardmap(spec: ModelSpec, ctx: ModelContext, params: dict,
                  h: jax.Array) -> jax.Array:
    mesh = ctx.mesh
    m_sz = mesh.shape["model"]
    e_pad = params["w_up"].shape[0]
    e_local = e_pad // m_sz
    b, s, _ = h.shape
    # Batch axes must divide the batch exactly inside shard_map (no GSPMD
    # padding there): greedily take pod/data axes that divide b; a
    # non-dividing remainder stays replicated (e.g. batch-1 long-context
    # decode replicates the token over the data axis).
    batch_axes = []
    dp = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape and b % (dp * mesh.shape[ax]) == 0:
            batch_axes.append(ax)
            dp *= mesh.shape[ax]
    n_loc = (b // dp) * s
    # §Perf: partition the (model-axis-replicated) local tokens across EP
    # ranks before routing when they divide evenly and aren't tiny.
    partition = (n_loc % m_sz == 0) and (n_loc // m_sz >= 8) \
        and ctx.moe_partition_tokens
    n_route = n_loc // m_sz if partition else n_loc
    cf = ctx.moe_capacity_factor
    c_send = _round_up(max(int(n_route * spec.moe.top_k * cf / m_sz), 1), 8)
    c_cap = _round_up(max(int(m_sz * c_send / e_local), 1), 8)

    x_spec = P(tuple(batch_axes) if batch_axes else None, None, None)
    param_specs = {
        "norm": P(None),
        "router": P(None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if "w_gate" in params:
        param_specs["w_gate"] = P("model", None, None)
    body_params = {k: params[k] for k in param_specs}

    body = functools.partial(_moe_shardmap_body, spec, e_local, c_send,
                             c_cap, m_sz, partition, "model")
    fn = jax.shard_map(body, mesh=mesh, in_specs=(param_specs, x_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(body_params, h)


def moe_block(spec: ModelSpec, ctx: ModelContext, params: dict,
              x: jax.Array) -> jax.Array:
    h = rms_norm(x, params["norm"])
    impl = ctx.moe_impl
    if impl == "auto":
        impl = "shardmap" if (ctx.mesh is not None
                              and "model" in ctx.mesh.shape
                              and ctx.mesh.shape["model"] > 1) else "dense"
    if impl == "shardmap":
        y = _moe_shardmap(spec, ctx, params, h)
    else:
        y = _moe_dense(spec, ctx, params, h)
    if spec.moe.shared_experts:
        shared_spec = spec.scaled(d_ff=spec.moe.shared_experts
                                  * spec.moe.d_ff_expert)
        y = y + mlp_block(shared_spec, ctx, params["shared"], h, norm=False)
    return ctx.shard(y, "batch", "seq_res", "act_embed")
