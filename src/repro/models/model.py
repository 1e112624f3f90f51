"""Top-level model: embedding -> decoder stack -> head, with the three entry
points the framework lowers (forward/loss for training, prefill and
decode_step for serving).

``build_model(spec, mesh, policy)`` works for every assigned architecture;
audio/VLM backbones take precomputed frontend embeddings (``embeds=``)
instead of token ids (the modality frontend is a stub per the assignment).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from ..core.modelspec import ModelSpec
from ..sharding import get_policy, tree_shardings
from .common import KeyGen, ModelContext, embed_init, rms_norm
from . import transformer as T


@dataclass(frozen=True)
class ModelCache:
    layers: Any  # stacked per-position caches
    lengths: jax.Array  # (B,) valid tokens per request
    #: (B, max_pages) int32 page table shared by all attention layers when
    #: the KV layout is paged (None for the dense layout); unused entries
    #: point at the reserved null page 0.
    page_table: jax.Array | None = None


jax.tree_util.register_dataclass(
    ModelCache, data_fields=["layers", "lengths", "page_table"],
    meta_fields=[])


@dataclass(frozen=True)
class Model:
    spec: ModelSpec
    ctx: ModelContext

    # -- init -----------------------------------------------------------------
    def init(self, rng: jax.Array) -> dict:
        spec, ctx = self.spec, self.ctx
        keys = KeyGen(rng)
        dtype = ctx.param_dtype
        n_shards = 1
        if ctx.mesh is not None and "model" in ctx.mesh.shape:
            n_shards = ctx.mesh.shape["model"]
        params: dict[str, Any] = {}
        # Decoder models own a token embedding even with a modality frontend
        # (a VLM decodes text tokens); encoder-only frontends (HuBERT) don't.
        if spec.frontend == "none" or spec.decoder:
            params["embed"] = embed_init(keys(), (spec.vocab, spec.d_model),
                                         dtype)
        params["layers"] = T.init_stack(spec, keys, dtype, n_shards)
        params["final_norm"] = jnp.ones((spec.d_model,), dtype)
        if not spec.tied_embeddings:
            params["lm_head"] = embed_init(keys(), (spec.d_model, spec.vocab),
                                           dtype)
        return params

    def param_axes(self) -> dict:
        spec = self.spec
        axes: dict[str, Any] = {}
        if spec.frontend == "none" or spec.decoder:
            axes["embed"] = ("vocab", "embed")
        axes["layers"] = T.stack_axes(spec)
        axes["final_norm"] = ("embed_vec",)
        if not spec.tied_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        return axes

    def param_shardings(self, mesh=None):
        mesh = mesh or self.ctx.mesh
        rules = dict(self.ctx.policy.rules)
        # weight-vector / derived logical axes
        rules.setdefault("embed_vec", None)
        rules.setdefault("qkv_heads", rules.get("heads"))
        rules.setdefault("kv_qkv", rules.get("kv_heads"))
        return tree_shardings(self.param_axes(), rules, mesh)

    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    def cache_axes(self) -> ModelCache:
        layout = self.ctx.cache_layout
        return ModelCache(
            layers=T.stack_cache_axes(self.spec, self.ctx.kv_quant,
                                      layout=layout),
            lengths=("batch",),
            page_table=("batch", None) if layout == "paged" else None)

    def cache_shardings(self, mesh=None):
        mesh = mesh or self.ctx.mesh
        rules = dict(self.ctx.policy.rules)
        rules.setdefault("embed_vec", None)
        return tree_shardings(self.cache_axes(), rules, mesh)

    # -- helpers ----------------------------------------------------------------
    def _embed_in(self, params, tokens=None, embeds=None):
        if embeds is not None:  # stub modality frontend: precomputed embeds
            return embeds.astype(self.ctx.compute_dtype)
        assert self.spec.frontend == "none" or self.spec.decoder, \
            "encoder-only frontend archs take embeds"
        return params["embed"][tokens].astype(self.ctx.compute_dtype)

    def _head_w(self, params):
        if self.spec.tied_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _logits(self, params, h):
        with jax.named_scope("head"):
            h = rms_norm(h, params["final_norm"])
            logits = h @ self._head_w(params)
            if self.ctx.tp_axis is not None \
                    and not self.spec.tied_embeddings:
                # vocab-sharded untied head: each rank holds (d, V/tp) —
                # the step's single logits gather (tied heads stay
                # replicated because the embedding table must serve
                # full-vocab lookups)
                with jax.named_scope("tp_gather"):
                    logits = jax.lax.all_gather(
                        logits, self.ctx.tp_axis, axis=logits.ndim - 1,
                        tiled=True)
            return self.ctx.shard(logits, "batch", "seq", "act_vocab")

    # -- training / encoder forward ---------------------------------------------
    def forward(self, params, tokens=None, *, embeds=None,
                positions=None) -> jax.Array:
        """Full pass returning logits for every position (small configs)."""
        x = self._embed_in(params, tokens, embeds)
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = self.ctx.shard(x, "batch", "seq_res", "act_embed")
        x, _ = T.apply_stack(self.spec, self.ctx, params["layers"], x,
                             positions)
        return self._logits(params, x)

    def loss(self, params, tokens=None, targets=None, *, embeds=None,
             mask=None, chunk: int = 512) -> jax.Array:
        """Mean next-token (or unit-prediction) cross entropy, computed in
        sequence chunks so the (B, S, V) logits never materialize."""
        x = self._embed_in(params, tokens, embeds)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = self.ctx.shard(x, "batch", "seq_res", "act_embed")
        x, _ = T.apply_stack(self.spec, self.ctx, params["layers"], x,
                             positions)
        x = rms_norm(x, params["final_norm"])
        w = self._head_w(params)
        if mask is None:
            mask = jnp.ones((b, s), jnp.float32)

        chunk = min(chunk, s)
        pad = (-s) % chunk
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            targets = jnp.pad(targets, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nc = (s + pad) // chunk
        xc = x.reshape(b, nc, chunk, -1).swapaxes(0, 1)
        tc = targets.reshape(b, nc, chunk).swapaxes(0, 1)
        mc = mask.reshape(b, nc, chunk).swapaxes(0, 1)

        @jax.checkpoint
        def chunk_loss(carry, xs):
            xb, tb, mb = xs
            logits = (xb @ w).astype(jnp.float32)
            logits = self.ctx.shard(logits, "batch", "seq", "act_vocab")
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tb[..., None],
                                         axis=-1)[..., 0]
            nll = (lse - picked) * mb
            return carry + nll.sum(), None

        total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32),
                                (xc, tc, mc))
        return total / jnp.maximum(mask.sum(), 1.0)

    # -- serving ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, *,
                   layout: str | None = None,
                   n_pages: int | None = None) -> ModelCache:
        """Serving cache.  ``layout`` defaults to the context's
        ``cache_layout``; for the paged layout ``n_pages`` sizes the pool
        (default: capacity-equivalent to the dense reservation, plus the
        null page)."""
        layout = layout or self.ctx.cache_layout
        if layout not in ("dense", "paged"):
            raise ValueError(f"unknown cache layout {layout!r}")
        page_table = None
        if layout == "paged":
            ps = self.ctx.kv_page_size
            if max_len % ps:
                raise ValueError(f"max_len {max_len} must be a multiple of "
                                 f"kv_page_size {ps}")
            max_pages = max_len // ps
            if n_pages is None:
                n_pages = batch * max_pages + 1  # +1: reserved null page
            page_table = jnp.zeros((batch, max_pages), jnp.int32)
        layers = T.init_stack_cache(self.spec, batch, max_len,
                                    self.ctx.compute_dtype,
                                    quantized=self.ctx.kv_quant,
                                    layout=layout,
                                    page_size=self.ctx.kv_page_size,
                                    n_pages=n_pages)
        return ModelCache(layers=layers,
                          lengths=jnp.zeros((batch,), jnp.int32),
                          page_table=page_table)

    def prefill(self, params, tokens=None, *, embeds=None, cache: ModelCache,
                lengths=None) -> tuple[jax.Array, ModelCache]:
        """Process the prompt, fill the cache, return last-position logits.

        ``lengths``: (B,) true prompt lengths (right padding allowed);
        defaults to the full width.
        """
        x = self._embed_in(params, tokens, embeds)
        b, s, _ = x.shape
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = self.ctx.shard(x, "batch", "seq_res", "act_embed")
        x, new_layers = T.apply_stack(self.spec, self.ctx, params["layers"],
                                      x, positions, cache=cache.layers,
                                      lengths=jnp.zeros((b,), jnp.int32))
        x = x[jnp.arange(b), lengths - 1]  # last valid position
        logits = self._logits(params, x[:, None])[:, 0]
        return logits, ModelCache(layers=new_layers, lengths=lengths,
                                  page_table=cache.page_table)

    def prefill_chunk(self, params, cache: ModelCache, tokens=None, *,
                      embeds=None) -> tuple[jax.Array, ModelCache]:
        """Chunked-prefill continuation (paper §IV-A): process the next
        ``chunk`` prompt tokens starting at each request's current
        ``cache.lengths`` offset.  Returns logits for the chunk's last
        position.  SSM states / token-shift caches carry forward, so this
        works for every architecture family."""
        x = self._embed_in(params, tokens, embeds)
        b, s, _ = x.shape
        positions = cache.lengths[:, None] + jnp.arange(s)[None, :]
        x = self.ctx.shard(x, "batch", "seq_res", "act_embed")
        x, new_layers = T.apply_stack(self.spec, self.ctx, params["layers"],
                                      x, positions, cache=cache.layers,
                                      lengths=cache.lengths,
                                      page_table=cache.page_table)
        logits = self._logits(params, x[:, -1:])[:, 0]
        return logits, ModelCache(layers=new_layers,
                                  lengths=cache.lengths + s,
                                  page_table=cache.page_table)

    def unified_step(self, params, cache: ModelCache, tokens: jax.Array,
                     positions: jax.Array, packed,
                     *, embeds=None) -> tuple[jax.Array, ModelCache]:
        """Token-packed unified serving step: every active slot's decode
        token plus every in-flight prompt's current prefill chunk ride one
        forward pass.  ``tokens``/``positions``: (T,) packed; ``packed``:
        the :class:`~repro.models.attention.PackedSegs` segment table.
        Prefill K/V are written directly into their pages by the packed
        attention path (no dense scratch cache).  Returns per-segment
        last-position logits (S, V) and the updated cache.  Requires the
        paged cache layout and an attention-only stack.
        """
        x = self._embed_in(params, tokens[None], embeds)
        x = self.ctx.shard(x, "batch", "seq_res", "act_embed")
        x, new_layers = T.apply_stack(self.spec, self.ctx, params["layers"],
                                      x, positions[None], cache=cache.layers,
                                      lengths=cache.lengths,
                                      page_table=cache.page_table,
                                      packed=packed)
        # each segment's logits come from its last valid packed position
        # (inactive segments produce garbage rows the engine ignores)
        last = packed.q_start + jnp.maximum(packed.q_len, 1) - 1
        h = jnp.take(x[0], last, axis=0)  # (S, D)
        logits = self._logits(params, h[None])[0]
        # keep slot lengths current for the segments that advanced (the
        # first max_slots segments are the decode slots, by layout)
        b = cache.lengths.shape[0]
        lengths = jnp.where(packed.q_len[:b] > 0,
                            packed.kv_len[:b].astype(cache.lengths.dtype),
                            cache.lengths)
        return logits, ModelCache(layers=new_layers, lengths=lengths,
                                  page_table=cache.page_table)

    def verify_step(self, params, cache: ModelCache, tokens: jax.Array,
                    positions: jax.Array, packed, *, n_decode: int,
                    width: int) -> tuple[jax.Array, jax.Array, ModelCache]:
        """Token-packed speculative verify step: like :meth:`unified_step`
        but the first ``n_decode`` segments are fixed-stride verify
        windows (``width`` = K+1 tokens: the slot's committed feed token
        followed by K draft proposals, causal within the window), and the
        target's logits are returned at *every* window position so the
        engine can accept/reject drafts on device.  Returns
        ``(dec_logits (n_decode, width, V), seg_logits (S, V), cache)``;
        ``seg_logits`` rows for the decode segments are the usual
        last-valid-position logits (used only by prefill sampling).

        ``cache.lengths`` is returned *unchanged* for the decode slots —
        the committed frontier depends on the accept counts, so the
        caller overwrites lengths after rejection sampling (rollback is
        pure length bookkeeping; rejected tokens' K/V stay in the pages
        and are masked by kv_len until overwritten).
        """
        x = self._embed_in(params, tokens[None], embeds=None)
        x = self.ctx.shard(x, "batch", "seq_res", "act_embed")
        x, new_layers = T.apply_stack(self.spec, self.ctx, params["layers"],
                                      x, positions[None], cache=cache.layers,
                                      lengths=cache.lengths,
                                      page_table=cache.page_table,
                                      packed=packed)
        # verify windows sit at packed offsets [0, n_decode * width) by
        # layout, so the per-position gather is a static reshape
        dec_h = x[0, :n_decode * width].reshape(n_decode, width, -1)
        dec_logits = self._logits(params, dec_h)
        last = packed.q_start + jnp.maximum(packed.q_len, 1) - 1
        h = jnp.take(x[0], last, axis=0)  # (S, D)
        seg_logits = self._logits(params, h[None])[0]
        return dec_logits, seg_logits, ModelCache(
            layers=new_layers, lengths=cache.lengths,
            page_table=cache.page_table)

    def decode_step(self, params, cache: ModelCache, tokens: jax.Array,
                    *, embeds=None) -> tuple[jax.Array, ModelCache]:
        """One autoregressive step.  tokens: (B, 1) -> logits (B, V)."""
        x = self._embed_in(params, tokens, embeds)
        b = x.shape[0]
        positions = cache.lengths[:, None]
        x = self.ctx.shard(x, "batch", "seq_res", "act_embed")
        x, new_layers = T.apply_stack(self.spec, self.ctx, params["layers"],
                                      x, positions, cache=cache.layers,
                                      lengths=cache.lengths,
                                      page_table=cache.page_table)
        logits = self._logits(params, x)[:, 0]
        return logits, ModelCache(layers=new_layers,
                                  lengths=cache.lengths + 1,
                                  page_table=cache.page_table)


def build_model(spec: ModelSpec, mesh=None, policy=None, **ctx_kw) -> Model:
    if isinstance(policy, str):
        policy = get_policy(policy)
    ctx = ModelContext(spec=spec, mesh=mesh,
                       policy=policy or get_policy("inference_tp"), **ctx_kw)
    return Model(spec=spec, ctx=ctx)
