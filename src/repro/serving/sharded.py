"""Mesh-sharded unified serving step: the engine's one-dispatch token-packed
forward threaded through ``shard_map`` over a (pp, tp) device mesh.

Sharding plan (Megatron-style, zero communication inside attention):

- tp axis splits *heads*: wq/wk/wv column-sharded so each rank computes
  ``n_heads/tp`` query heads against its own ``n_kv_heads/tp`` KV heads;
  the paged KV pools shard on their kv-head axis, so page ids (and the
  page table, replicated) are valid on every rank — each shard's ragged
  paged-attention kernel walks the same table into its local pool slice.
  wo / w_down are row-sharded: the partial products ``psum`` once per
  column/row pair — exactly two all-reduces per layer.  An untied lm_head
  is vocab-sharded with one tiled ``all_gather`` of the (S, V/tp) logits.
- pp axis splits the stacked ``repeats`` layer axis of both params and KV
  pools.  The step runs a masked commit ring: every rank executes its
  local sub-stack each stage (``lax.scan`` infers the trip count from the
  leaf shapes, so the stack code is untouched), but only the rank whose
  stage it is commits its KV writes and forwards its activation via
  ``ppermute`` — pp point-to-point hops plus one broadcast psum per step.

Sampling runs replicated on every rank from the same key, so the sampled
(S,) vector is identical everywhere and the host pulls it once — the
one-dispatch / one-transfer-per-step invariant holds per host.  Greedy
outputs are asserted token-identical to the tp=pp=1 engine (fp32 psum
reduction order is deterministic per shape on the CPU backend).

CPU meshes come from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
set before importing jax (tests use subprocesses; CI exports it).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer as T
from ..models.attention import PackedSegs
from ..models.model import Model, ModelCache
from .sampling import sample_slots

TP_AXIS = "tp"
PP_AXIS = "pp"
#: parallelism axes the live engine can lower (everything else runs
#: analytically only)
SUPPORTED_AXES = ("tp", "pp")

#: logical param axis -> mesh axis.  "vocab" shards the untied lm_head;
#: the embedding table is forced replicated afterwards (token lookups
#: index the full vocab on every rank).
_PARAM_RULES = {"qkv_heads": TP_AXIS, "kv_qkv": TP_AXIS, "mlp": TP_AXIS,
                "vocab": TP_AXIS, "layers": PP_AXIS}
#: logical cache axis -> mesh axis: pools split on kv-heads (tp) and the
#: stacked layer repeats (pp); lengths and the page table stay replicated.
_CACHE_RULES = {"act_kv_heads": TP_AXIS, "layers": PP_AXIS}


def _is_axes(x) -> bool:
    """Leaf predicate for axis-name tuples inside param/cache axis trees."""
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def _rules(base: dict, tp: int, pp: int) -> dict:
    """Drop degree-1 mesh axes: shard_map normalizes a trivial axis out
    of its output shardings, so keeping it in the input specs would make
    the second dispatch's cache key differ from the first's."""
    return {k: v for k, v in base.items()
            if (v != TP_AXIS or tp > 1) and (v != PP_AXIS or pp > 1)}


def _to_pspec(axes: tuple, rules: dict) -> P:
    names = [rules.get(name) for name in axes]
    while names and names[-1] is None:
        # trailing Nones are implicit — stripping them makes replicated
        # leaves spell P() exactly like every ad-hoc upload, so the jit
        # cache key never sees two spellings of the same sharding
        names.pop()
    return P(*names)


def validate_engine_sharding(spec, config) -> None:
    """Raise ``ValueError`` for any (tp, pp) the live engine cannot lower
    against ``spec``.  Shape divisibility is checked before device count
    so misconfigurations fail identically on any host."""
    tp, pp = config.tp, config.pp
    if tp < 1 or pp < 1:
        raise ValueError(f"EngineConfig tp/pp must be >= 1, got "
                         f"tp={tp} pp={pp}")
    if tp * pp == 1:
        return
    if not config.unified:
        raise ValueError(
            "tp/pp > 1 requires unified=True: only the token-packed "
            "one-dispatch step is threaded through shard_map")
    if getattr(config, "n_spec", 0):
        raise ValueError(
            f"n_spec={config.n_spec} with tp={tp} pp={pp}: speculative "
            "decoding is single-device only — the fused draft/verify step "
            "is not threaded through build_sharded_step yet (the draft "
            "pool and accept/reject would need their own shard_map "
            "plumbing)")
    if any(k != "attn" for k in spec.layer_kinds()) \
            or spec.moe is not None:
        raise ValueError(
            f"tp/pp > 1 supports dense attention-only stacks; "
            f"{spec.name!r} has non-attention or MoE layers (route MoE "
            "through ep — analytical backend only)")
    if tp > 1:
        for field_name, val in (("n_heads", spec.n_heads),
                                ("n_kv_heads", spec.n_kv_heads),
                                ("d_ff", spec.d_ff)):
            if val % tp:
                raise ValueError(
                    f"tp={tp} must divide {field_name}={val} "
                    f"({spec.name!r}): heads/FFN shard column-wise")
        if not spec.tied_embeddings and spec.vocab % tp:
            raise ValueError(
                f"tp={tp} must divide vocab={spec.vocab} ({spec.name!r}): "
                "the untied lm_head is vocab-sharded")
    if pp > 1:
        _, repeats = T.stack_period(spec)
        if repeats % pp:
            raise ValueError(
                f"pp={pp} must divide the stacked layer repeats={repeats} "
                f"({spec.name!r})")
    n_dev = jax.device_count()
    if n_dev < tp * pp:
        raise ValueError(
            f"tp={tp} x pp={pp} needs {tp * pp} devices but only {n_dev} "
            "are visible; on CPU export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={tp * pp} before "
            "importing jax")


def make_engine_mesh(tp: int, pp: int) -> Mesh:
    """(pp, tp) mesh over the first tp*pp devices (``jax.make_mesh`` wants
    every device; serving meshes may be a subset)."""
    devs = np.array(jax.devices()[:tp * pp]).reshape(pp, tp)
    return Mesh(devs, (PP_AXIS, TP_AXIS))


def local_spec(spec, tp: int):
    """The per-rank model geometry a shard_map worker computes with."""
    if tp == 1:
        return spec
    return dataclasses.replace(spec, n_heads=spec.n_heads // tp,
                               n_kv_heads=spec.n_kv_heads // tp,
                               d_ff=spec.d_ff // tp)


def param_pspecs(model: Model, tp: int, pp: int):
    """PartitionSpec tree matching ``model.param_axes()``; the embedding
    table is replicated regardless of the vocab rule (see module doc)."""
    rules = _rules(_PARAM_RULES, tp, pp)
    specs = jax.tree.map(lambda a: _to_pspec(a, rules),
                         model.param_axes(), is_leaf=_is_axes)
    if "embed" in specs:
        specs["embed"] = P()
    return specs


def cache_pspecs(model: Model, tp: int, pp: int):
    """PartitionSpec tree matching ``model.cache_axes()`` (pools split on
    kv-heads/layers; lengths + page table replicated, so host page ids
    are valid on every shard)."""
    rules = _rules(_CACHE_RULES, tp, pp)
    return jax.tree.map(lambda a: _to_pspec(a, rules),
                        model.cache_axes(), is_leaf=_is_axes)


def shard_tree(pytree, pspecs, mesh: Mesh):
    """``device_put`` every leaf with its NamedSharding (replicates the
    host/single-device copy onto the mesh, splitting sharded axes)."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        pytree, pspecs)


def init_sharded(init, pspecs, mesh: Mesh):
    """Run ``init()`` with every output leaf created already split over
    the mesh by its PartitionSpec: nothing is materialised whole on one
    device first (a model too large for one chip fits this way)."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(init, out_shardings=shardings)()


def collective_stats(spec, tp: int, pp: int, t_pack: int, n_segs: int,
                     dtype_bytes: int = 4) -> tuple[int, int]:
    """(collectives_per_step, estimated all-reduce bytes per step) for one
    packed step of ``t_pack`` tokens — the measured column next to the
    analytical network model's message-size terms.

    Counts per device: 2 psums per layer when tp>1 (each moving
    ~2*(tp-1)/tp of the (T, d_model) residual in a ring), pp ppermute
    hops + 1 broadcast psum when pp>1, and one logits all_gather when the
    head is untied ((tp-1)/tp of (S, V) received per rank)."""
    coll = 0
    bytes_ = 0.0
    if tp > 1:
        n_ar = 2 * spec.n_layers
        payload = t_pack * spec.d_model * dtype_bytes
        coll += n_ar
        bytes_ += n_ar * 2.0 * (tp - 1) / tp * payload
        if not spec.tied_embeddings:
            coll += 1
            bytes_ += (tp - 1) / tp * n_segs * spec.vocab * dtype_bytes
    if pp > 1:
        coll += pp + 1  # ring hops + final broadcast psum
        hop = t_pack * spec.d_model * dtype_bytes
        bytes_ += pp * hop + 2.0 * (pp - 1) / pp * hop
    return coll, int(bytes_)


def _sharded_parts(model: Model, mesh: Mesh, tp: int, pp: int, *,
                   max_q: int, n_decode: int):
    """The per-rank packed forward shared by :func:`build_sharded_step`
    and :func:`build_sharded_forward`: ``forward(params, cache, tokens,
    positions, q_start, q_len, kv_len, seg_ptab) -> (logits, new_cache)``
    runs inside ``shard_map``; also returns the param/cache spec trees."""
    lspec = local_spec(model.spec, tp)
    # worker-local context: mesh=None (GSPMD constraints are meaningless
    # inside shard_map), tp psums via the named axis
    lctx = model.ctx.with_(spec=lspec, mesh=None,
                           tp_axis=TP_AXIS if tp > 1 else None)
    lmodel = Model(spec=lspec, ctx=lctx)

    def forward(params, cache, tokens, positions, q_start, q_len, kv_len,
                seg_ptab):
        packed = PackedSegs(q_start=q_start, q_len=q_len, kv_len=kv_len,
                            page_table=seg_ptab, max_q=max_q,
                            n_decode=n_decode)
        x = lmodel._embed_in(params, tokens[None])
        layers = cache.layers
        for stage in range(pp):  # static: the ring is part of the program
            y, new_layers = T.apply_stack(
                lspec, lctx, params["layers"], x, positions[None],
                cache=layers, lengths=cache.lengths,
                page_table=cache.page_table, packed=packed)
            if pp == 1:
                layers, x = new_layers, y
                continue
            # masked commit: every rank ran its local sub-stack, but only
            # the rank whose stage this is keeps the KV writes and
            # forwards its activation around the ring
            on_stage = jax.lax.axis_index(PP_AXIS) == stage
            layers = jax.tree.map(lambda n, o: jnp.where(on_stage, n, o),
                                  new_layers, layers)
            x = jax.lax.ppermute(
                jnp.where(on_stage, y, x), PP_AXIS,
                [(i, (i + 1) % pp) for i in range(pp)])
        if pp > 1:
            # after the last hop rank 0 holds the final hidden state:
            # broadcast it so sampling stays replicated
            x = jax.lax.psum(
                jnp.where(jax.lax.axis_index(PP_AXIS) == 0, x,
                          jnp.zeros_like(x)), PP_AXIS)
        last = packed.q_start + jnp.maximum(packed.q_len, 1) - 1
        h = jnp.take(x[0], last, axis=0)
        logits = lmodel._logits(params, h[None])[0]
        b = cache.lengths.shape[0]
        lengths = jnp.where(packed.q_len[:b] > 0,
                            packed.kv_len[:b].astype(cache.lengths.dtype),
                            cache.lengths)
        return logits, ModelCache(layers=layers, lengths=lengths,
                                  page_table=cache.page_table)

    return forward, param_pspecs(model, tp, pp), cache_pspecs(model, tp, pp)


def build_sharded_forward(model: Model, mesh: Mesh, tp: int, pp: int, *,
                          max_q: int, n_decode: int):
    """The sharded twin of ``Model.unified_step``: one jitted dispatch
    ``(params, cache, tokens, positions, q_start, q_len, kv_len, seg_ptab)
    -> (logits (S, V), new_cache)``, logits replicated.  What a logits
    comparison against the one-device step runs."""
    forward, p_specs, c_specs = _sharded_parts(
        model, mesh, tp, pp, max_q=max_q, n_decode=n_decode)
    rep = P()
    return jax.jit(jax.shard_map(
        forward, mesh=mesh, in_specs=(p_specs, c_specs) + (rep,) * 6,
        out_specs=(rep, c_specs), check_vma=False), donate_argnums=(1,))


def build_sharded_step(model: Model, mesh: Mesh, tp: int, pp: int, *,
                       max_slots: int, max_q: int, n_decode: int):
    """The sharded twin of ``ServeEngine._unified_and_sample``: same
    signature, same (sampled, decode_feed, new_cache) result, one jitted
    dispatch.  Closes over the static packed profile (max_q, n_decode)
    exactly like the single-device jits, so nothing retraces."""
    forward, p_specs, c_specs = _sharded_parts(
        model, mesh, tp, pp, max_q=max_q, n_decode=n_decode)
    rep = P()

    def worker(params, cache, tokens, positions, q_start, q_len, kv_len,
               seg_ptab, key_data, temps, topks, topps):
        logits, new_cache = forward(params, cache, tokens, positions,
                                    q_start, q_len, kv_len, seg_ptab)
        step_key = jax.random.wrap_key_data(key_data)
        keys = jax.random.split(step_key, q_len.shape[0])
        toks = sample_slots(logits, keys, temps, topks, topps)
        return toks, toks[:max_slots], new_cache

    inner = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(p_specs, c_specs) + (rep,) * 10,
        out_specs=(rep, rep, c_specs), check_vma=False)

    def stepped(params, cache, tokens, positions, q_start, q_len, kv_len,
                seg_ptab, step_key, temps, topks, topps):
        # typed PRNG keys don't pass through shard_map on every jax
        # version: round-trip the raw key data (wrap happens per-rank)
        return inner(params, cache, tokens, positions, q_start, q_len,
                     kv_len, seg_ptab, jax.random.key_data(step_key),
                     temps, topks, topps)

    return jax.jit(stepped, donate_argnums=(1,))
