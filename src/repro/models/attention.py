"""Multi-head / grouped-query attention layer with KV cache.

Three execution paths over dense K/V, selected by
``ModelContext.attn_impl``:

  direct : plain einsum softmax (small sequences, and the decode step)
  flash  : scan-based blockwise attention (``repro.kernels.flash_jnp``) —
           memory-bounded, custom VJP; what the dry run lowers
  pallas : the TPU Pallas kernel (``repro.kernels.flash_attention``)

The paged decode and token-packed paths run the Pallas paged/ragged
kernels on TPU and their gather oracles elsewhere
(:meth:`ModelContext.paged_kernel`); Pallas runs compiled on TPU and in
interpret mode on CPU.

KV cache layouts (``ModelContext.cache_layout``):

  dense : (B, T_max, Hkv, Dh) per layer, left-aligned with a shared
          per-request ``lengths`` vector.  Decode inserts at position
          ``lengths`` and attends with a kv_len mask — GSPMD turns this
          into head-sharded or sequence-sharded attention depending on the
          sharding policy.
  paged : a flat (n_pages, Hkv, page_size, Dh) pool per layer — the
          *resident* layout, head axis ahead of the page-token axis so one
          (page, head) tile is a contiguous kernel block and no per-call
          transpose is needed — plus a (B, max_pages) page-table
          indirection shared across layers (:class:`PagedAttnCache`; the
          host half is :mod:`repro.serving.paging`).  Decode scatters the
          new token into its slot's current page and attends against the
          pages the page table names — capacity scales with tokens *used*,
          not slots reserved.  The int8 ``k_scale`` quantized path is
          preserved (scale pools page alongside the values).

Token-packed unified step (:class:`PackedSegs`): the serving engine packs
every active slot's decode token and every in-flight prompt's current
prefill chunk into one ragged (T,) batch; the packed path below writes
each token's K/V **directly into its request's pages** (no dense scratch
cache, no insert-time scatter) and runs one ragged paged-attention
dispatch over all segments.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..core.modelspec import ModelSpec
from ..kernels import ops as kops
from ..kernels.ref import paged_gather
from .common import KeyGen, ModelContext, apply_rope, dense_init, rms_norm


@dataclass(frozen=True)
class PackedSegs:
    """Segment table of one token-packed unified step (a pytree).

    The packed query batch concatenates S *segments* — one per decode slot
    and one per prefill row, at fixed, nondecreasing token offsets — so a
    single dispatch serves every active request.  ``max_q`` (static) is
    the widest segment the layout allows (the engine's chunk size).

    ``n_decode`` (static) tells the attention path that the first
    ``n_decode`` segments are fixed-width decode slots sitting at packed
    offsets [0, n_decode * decode_q): it then runs them as a
    max_q=decode_q sub-batch inside the same dispatch, so decode slots
    never pay a chunk-wide padded query tile.  0 means no static split is
    known (generic ragged packing).  ``decode_q`` (static) is the decode
    segment stride — 1 for plain decode, K+1 for speculative verify
    segments (one committed token + K draft tokens, causal within the
    segment).
    """
    q_start: jax.Array  # (S,) int32 token offset of each segment's queries
    q_len: jax.Array  # (S,) int32 new tokens this step (0 = inactive)
    kv_len: jax.Array  # (S,) int32 valid KV tokens *after* this step
    page_table: jax.Array  # (S, max_pages) int32 pages each segment owns
    max_q: int = 1
    n_decode: int = 0
    decode_q: int = 1

    @property
    def n_segs(self) -> int:
        return self.page_table.shape[0]


jax.tree_util.register_dataclass(
    PackedSegs, data_fields=["q_start", "q_len", "kv_len", "page_table"],
    meta_fields=["max_q", "n_decode", "decode_q"])


def init_attention(spec: ModelSpec, keys: KeyGen, dtype) -> dict:
    d, hq, hkv, dh = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.d_head
    p = {
        "norm": jnp.ones((d,), dtype),
        "wq": dense_init(keys(), (d, hq * dh), dtype),
        "wk": dense_init(keys(), (d, hkv * dh), dtype),
        "wv": dense_init(keys(), (d, hkv * dh), dtype),
        "wo": dense_init(keys(), (hq * dh, d), dtype),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def attention_axes(spec: ModelSpec) -> dict:
    axes = {
        "norm": ("embed_vec",),
        "wq": ("embed", "qkv_heads"),
        "wk": ("embed", "kv_qkv"),
        "wv": ("embed", "kv_qkv"),
        "wo": ("qkv_heads", "embed"),
    }
    if spec.qkv_bias:
        axes.update({"bq": ("qkv_heads",), "bk": ("kv_qkv",),
                     "bv": ("kv_qkv",)})
    return axes


@dataclass(frozen=True)
class AttnCache:
    """Per-layer KV cache (a pytree).

    With int8 quantization (paper Table V's lossy KV bucket; our §Perf
    iteration) ``k``/``v`` are int8 and ``k_scale``/``v_scale`` hold the
    per-(token, head) absmax/127 scales — halving the decode stream vs
    bf16.  Scale fields are None for the full-precision cache.
    """
    k: jax.Array  # (B, T, Hkv, Dh)
    v: jax.Array
    k_scale: jax.Array | None = None  # (B, T, Hkv) f32
    v_scale: jax.Array | None = None


def init_attn_cache(spec: ModelSpec, batch: int, max_len: int, dtype,
                    quantized: bool = False) -> AttnCache:
    shape = (batch, max_len, spec.n_kv_heads, spec.d_head)
    if quantized:
        sshape = (batch, max_len, spec.n_kv_heads)
        return AttnCache(k=jnp.zeros(shape, jnp.int8),
                         v=jnp.zeros(shape, jnp.int8),
                         k_scale=jnp.zeros(sshape, jnp.float32),
                         v_scale=jnp.zeros(sshape, jnp.float32))
    return AttnCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


jax.tree_util.register_dataclass(
    AttnCache, data_fields=["k", "v", "k_scale", "v_scale"], meta_fields=[])


@dataclass(frozen=True)
class PagedAttnCache:
    """Per-layer paged KV pool (a pytree).

    ``k``/``v`` are (n_pages, Hkv, page_size, Dh) — the resident layout:
    the head axis sits ahead of the page-token axis so one (page, head)
    tile is a contiguous block and the Pallas kernels consume the pools
    without a per-call transpose.  Which pages belong to which request is
    the engine's page table (carried in ``ModelCache.page_table``, shared
    by every attention layer).  Page 0 is the reserved null page (see
    :mod:`repro.serving.paging`).  With int8 quantization the
    (n_pages, Hkv, page_size) scale pools ride along, exactly like the
    dense layout's scale planes.
    """
    k: jax.Array  # (P, Hkv, page_size, Dh)
    v: jax.Array
    k_scale: jax.Array | None = None  # (P, Hkv, page_size) f32
    v_scale: jax.Array | None = None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]


jax.tree_util.register_dataclass(
    PagedAttnCache, data_fields=["k", "v", "k_scale", "v_scale"],
    meta_fields=[])


def init_paged_attn_cache(spec: ModelSpec, n_pages: int, page_size: int,
                          dtype, quantized: bool = False) -> PagedAttnCache:
    shape = (n_pages, spec.n_kv_heads, page_size, spec.d_head)
    if quantized:
        sshape = (n_pages, spec.n_kv_heads, page_size)
        return PagedAttnCache(k=jnp.zeros(shape, jnp.int8),
                              v=jnp.zeros(shape, jnp.int8),
                              k_scale=jnp.zeros(sshape, jnp.float32),
                              v_scale=jnp.zeros(sshape, jnp.float32))
    return PagedAttnCache(k=jnp.zeros(shape, dtype),
                          v=jnp.zeros(shape, dtype))


def paged_insert_rows(paged: PagedAttnCache, dense: AttnCache, row,
                      pages: jax.Array) -> PagedAttnCache:
    """Scatter one dense scratch row into the pool pages named by ``pages``.

    ``dense`` is a (R, T, Hkv, Dh) scratch cache (the engine's prefill
    scratch), ``row`` a traced row index, ``pages`` the (max_pages,) page
    ids covering that request (0-padded: the tail of the scratch row is
    zeros and lands on the null page).  T must equal max_pages * page_size.
    """
    ps = paged.page_size

    def scat(pool, scr):
        col = jax.lax.dynamic_slice_in_dim(scr, row, 1, axis=0)[0]  # (T,...)
        chunks = col.reshape((pages.shape[0], ps) + col.shape[1:])
        # (mp, ps, Hkv, ...) -> the pool's resident (mp, Hkv, ps, ...)
        chunks = jnp.swapaxes(chunks, 1, 2)
        return pool.at[pages].set(chunks.astype(pool.dtype),
                                  mode="drop", unique_indices=False)

    quant = paged.k_scale is not None
    return PagedAttnCache(
        k=scat(paged.k, dense.k), v=scat(paged.v, dense.v),
        k_scale=scat(paged.k_scale, dense.k_scale) if quant else None,
        v_scale=scat(paged.v_scale, dense.v_scale) if quant else None)




def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B, S, H, D) -> int8 values + (B, S, H) scales."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _project_qkv(spec: ModelSpec, ctx: ModelContext, params, x, positions):
    b, s, _ = x.shape
    hq, hkv, dh = spec.n_heads, spec.n_kv_heads, spec.d_head
    h = rms_norm(x, params["norm"])
    q = h @ params["wq"]
    k = h @ params["wk"]
    v = h @ params["wv"]
    if spec.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, hq, dh)
    k = k.reshape(b, s, hkv, dh)
    v = v.reshape(b, s, hkv, dh)
    if spec.pos == "rope":
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    q = ctx.shard(q, "batch", "seq", "act_heads", None)
    k = ctx.shard(k, "batch", "seq", "act_kv_heads", None)
    v = ctx.shard(v, "batch", "seq", "act_kv_heads", None)
    return q, k, v


def _attend(spec: ModelSpec, ctx: ModelContext, q, k, v, *, causal,
            kv_len=None, q_offset=0):
    window = spec.attn.window if spec.attn.kind == "swa" else None
    sq, skv = q.shape[1], k.shape[1]
    impl = ctx.attn_impl
    if impl in ("auto", "gather"):  # "gather" names the paged oracle only
        # direct path materializes (B, H, Sq, Skv) scores: only for short
        # full passes and single-token decode steps.
        impl = "direct" if (sq * skv <= 1024 * 1024 and sq > 1) or sq <= 16 \
            else "flash"
    if impl in ("flash", "pallas") and ctx.mesh is not None \
            and k.shape[2] < q.shape[2]:
        # GQA under TP: the blockwise kernels regroup q as (B, Hkv, G, S, D),
        # and with Hkv < model-axis size GSPMD has no consistent layout for
        # that split — it falls back to re-gathering Q inside every kv-block
        # loop step.  Expanding K/V to the full head count restores a clean
        # single-dimension head sharding (q-heads padded at worst); the K/V
        # duplication is fresh-activation-sized (not the KV cache) and the
        # Pallas TPU kernel avoids it entirely on real hardware.
        g = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        k = ctx.shard(k, "batch", "seq", "act_heads", None)
        v = ctx.shard(v, "batch", "seq", "act_heads", None)
    return kops.multi_head_attention(
        q, k, v, causal=causal, window=window, kv_len=kv_len,
        q_offset=q_offset, impl=impl, block_q=ctx.flash_block_q,
        block_kv=ctx.flash_block_kv, causal_skip=ctx.flash_causal_skip)


def _paged_attention(spec: ModelSpec, ctx: ModelContext, cache:
                     "PagedAttnCache", q, k, v, lengths, page_table):
    """Paged decode step: scatter the new token's K/V into its page, then
    attend against the pages the table names.  Numerically identical to the
    dense decode path (same insert-then-masked-attend order; the gathered
    view has the same width max_pages * page_size as a dense cache row)."""
    b = q.shape[0]
    ps = cache.page_size
    max_pages = page_table.shape[1]
    quant = cache.k_scale is not None
    if quant:
        k_store, k_sc = _quantize_kv(k)
        v_store, v_sc = _quantize_kv(v)
    else:
        k_store, v_store = k.astype(cache.k.dtype), v.astype(cache.v.dtype)

    # page/offset of the token being written (position == lengths); clamp
    # the page index so garbage slots past max_seq stay in bounds (their
    # table entries point at the null page anyway).
    page_idx = jnp.minimum(lengths // ps, max_pages - 1)
    page_ids = jnp.take_along_axis(page_table, page_idx[:, None],
                                   axis=1)[:, 0]
    offs = lengths % ps

    def scat(pool, t):  # t: (B, 1, Hkv, ...) new-token values
        # resident pool layout (P, Hkv, ps, ...): token offset indexes the
        # axis *behind* the heads
        return pool.at[page_ids, :, offs].set(t[:, 0].astype(pool.dtype),
                                              mode="drop",
                                              unique_indices=False)

    with jax.named_scope("kv_write"):
        kc, vc = scat(cache.k, k_store), scat(cache.v, v_store)
        new_cache = PagedAttnCache(
            k=kc, v=vc,
            k_scale=scat(cache.k_scale, k_sc) if quant else None,
            v_scale=scat(cache.v_scale, v_sc) if quant else None)

    impl, interpret = ctx.paged_kernel()
    if impl == "pallas" and not quant:
        o = kops.paged_decode_attention(q, kc, vc, page_table, lengths + 1,
                                        impl="pallas", interpret=interpret)
    else:
        ka = paged_gather(kc, page_table)
        va = paged_gather(vc, page_table)
        if quant:
            ka = _dequantize_kv(ka, paged_gather(new_cache.k_scale,
                                                 page_table), k.dtype)
            va = _dequantize_kv(va, paged_gather(new_cache.v_scale,
                                                 page_table), v.dtype)
        o = _attend(spec, ctx, q, ka, va, causal=spec.attn.causal,
                    kv_len=lengths + 1, q_offset=lengths)
    return o, new_cache


def _packed_paged_attention(spec: ModelSpec, ctx: ModelContext,
                            cache: "PagedAttnCache", q, k, v,
                            packed: PackedSegs):
    """Token-packed unified step: write every packed token's K/V directly
    into its request's pages (position ``kv_len - q_len + i`` for token i
    of its segment; tokens outside any live segment land on the null
    page), then one ragged paged-attention dispatch attends each segment
    against exactly the pages it owns.  Numerically identical to running
    each segment through the dense chunked-prefill / paged decode paths:
    same insert-then-masked-attend order, same page linearization.
    """
    ps = cache.page_size
    t = q.shape[1]
    s_count, max_pages = packed.page_table.shape
    quant = cache.k_scale is not None
    if quant:
        k_store, k_sc = _quantize_kv(k)
        v_store, v_sc = _quantize_kv(v)
    else:
        k_store, v_store = k.astype(cache.k.dtype), v.astype(cache.v.dtype)

    # per-token destination page/offset, derived from the segment table
    # (q_start is nondecreasing by construction)
    tok = jnp.arange(t)
    seg = jnp.clip(jnp.searchsorted(packed.q_start, tok, side="right") - 1,
                   0, s_count - 1)
    off_in_seg = tok - packed.q_start[seg]
    valid = (off_in_seg >= 0) & (off_in_seg < packed.q_len[seg])
    pos = packed.kv_len[seg] - packed.q_len[seg] + off_in_seg
    pos = jnp.clip(pos, 0, max_pages * ps - 1)
    page_ids = jnp.where(valid, packed.page_table[seg, pos // ps], 0)
    offs = pos % ps

    def scat(pool, tnew):  # tnew: (1, T, Hkv, ...) packed new values
        return pool.at[page_ids, :, offs].set(tnew[0].astype(pool.dtype),
                                              mode="drop",
                                              unique_indices=False)

    with jax.named_scope("kv_write"):
        kc, vc = scat(cache.k, k_store), scat(cache.v, v_store)
        new_cache = PagedAttnCache(
            k=kc, v=vc,
            k_scale=scat(cache.k_scale, k_sc) if quant else None,
            v_scale=scat(cache.v_scale, v_sc) if quant else None)

    # the ragged kernel takes no scale operands: int8 KV keeps the oracle
    impl, interpret = ctx.paged_kernel()
    ka, va = kc, vc
    if quant:
        impl, interpret = "gather", False
        ka = (kc.astype(jnp.float32)
              * new_cache.k_scale[..., None]).astype(k.dtype)
        va = (vc.astype(jnp.float32)
              * new_cache.v_scale[..., None]).astype(v.dtype)

    nd = packed.n_decode
    dq = packed.decode_q
    if 0 < nd < s_count and packed.max_q > dq:
        # static decode/prefill split (same dispatch, two sub-batches):
        # the nd decode segments run at max_q=decode_q (1 for plain
        # decode, K+1 for speculative verify windows) instead of dragging
        # a chunk-wide padded query tile through the kernel
        o_dec = kops.ragged_paged_attention(
            q[0, :nd * dq], ka, va, packed.page_table[:nd],
            packed.q_start[:nd], packed.q_len[:nd], packed.kv_len[:nd],
            max_q=dq, impl=impl, interpret=interpret)
        o_pre = kops.ragged_paged_attention(
            q[0, nd * dq:], ka, va, packed.page_table[nd:],
            packed.q_start[nd:] - nd * dq, packed.q_len[nd:],
            packed.kv_len[nd:], max_q=packed.max_q, impl=impl,
            interpret=interpret)
        o = jnp.concatenate([o_dec, o_pre], axis=0)
    else:
        o = kops.ragged_paged_attention(
            q[0], ka, va, packed.page_table, packed.q_start, packed.q_len,
            packed.kv_len, max_q=packed.max_q, impl=impl,
            interpret=interpret)
    return o[None], new_cache


def attention_block(spec: ModelSpec, ctx: ModelContext, params: dict,
                    x: jax.Array, positions: jax.Array,
                    cache: AttnCache | PagedAttnCache | None = None,
                    lengths: jax.Array | None = None,
                    page_table: jax.Array | None = None,
                    packed: PackedSegs | None = None
                    ) -> tuple[jax.Array, AttnCache | PagedAttnCache | None]:
    """x: (B, S, D).  Five modes:

      * full pass (cache None): training / encoder forward,
      * prefill (dense cache, lengths == 0): fills cache[0:S],
      * decode  (dense cache, S == 1): inserts at ``lengths`` and attends
        against the cache prefix,
      * paged decode (PagedAttnCache, S == 1): scatters into the slot's
        current page and attends via the page table,
      * packed unified step (PagedAttnCache + ``packed``): x is the
        (1, T, D) token-packed mixed decode+prefill batch; K/V go directly
        to pages and one ragged dispatch serves every segment.
    """
    b, s, _ = x.shape
    q, k, v = _project_qkv(spec, ctx, params, x, positions)

    new_cache = None
    if cache is None:
        o = _attend(spec, ctx, q, k, v, causal=spec.attn.causal)
    elif isinstance(cache, PagedAttnCache) and packed is not None:
        if spec.attn.kind == "swa":
            raise NotImplementedError(
                "the packed unified step has no sliding-window masking")
        o, new_cache = _packed_paged_attention(spec, ctx, cache, q, k, v,
                                               packed)
    elif isinstance(cache, PagedAttnCache):
        assert s == 1, "the paged layout serves single-token decode; " \
            "prefill runs on a dense scratch cache and is paged at insert"
        assert lengths is not None and page_table is not None
        o, new_cache = _paged_attention(spec, ctx, cache, q, k, v, lengths,
                                        page_table)
    else:
        # Unified cached path covering prefill (lengths=0), chunked-prefill
        # continuation (lengths=offset, s=chunk) and decode (s=1): insert the
        # s new K/V rows at each request's `lengths` offset (in-place under
        # donation), then attend causally against the valid prefix.
        assert lengths is not None
        quant = cache.k_scale is not None
        if quant:
            k_store, k_sc = _quantize_kv(k)
            v_store, v_sc = _quantize_kv(v)
        else:
            k_store, v_store = k.astype(cache.k.dtype), v.astype(cache.v.dtype)

        if s == cache.k.shape[1]:  # full-width prefill: static insert
            full = lambda c, t: jax.lax.dynamic_update_slice(
                c, t, (0,) * c.ndim)
            kc, vc = full(cache.k, k_store), full(cache.v, v_store)
            if quant:
                ksc = full(cache.k_scale, k_sc)
                vsc = full(cache.v_scale, v_sc)
        else:
            ins = jax.vmap(lambda c, t, p: jax.lax.dynamic_update_slice(
                c, t, (p,) + (0,) * (c.ndim - 1)))
            kc, vc = ins(cache.k, k_store, lengths), \
                ins(cache.v, v_store, lengths)
            if quant:
                ksc = ins(cache.k_scale, k_sc, lengths)
                vsc = ins(cache.v_scale, v_sc, lengths)
        kc = ctx.shard(kc, "batch", "kv_seq", "act_kv_heads", None)
        vc = ctx.shard(vc, "batch", "kv_seq", "act_kv_heads", None)
        new_cache = AttnCache(k=kc, v=vc,
                              k_scale=ksc if quant else None,
                              v_scale=vsc if quant else None)
        if s == cache.k.shape[1]:
            # fresh full-width prefill: attend over the new tokens directly
            o = _attend(spec, ctx, q, k, v, causal=spec.attn.causal)
        else:
            ka, va = kc, vc
            if quant:
                ka = _dequantize_kv(kc, ksc, k.dtype)
                va = _dequantize_kv(vc, vsc, v.dtype)
            o = _attend(spec, ctx, q, ka, va, causal=spec.attn.causal,
                        kv_len=lengths + s, q_offset=lengths)

    o = ctx.shard(o, "batch", "seq", "act_heads", None)
    o = o.reshape(b, s, spec.n_heads * spec.d_head)
    y = o @ params["wo"]
    if ctx.tp_axis is not None:
        # column-sharded wq/wk/wv gave this rank n_heads/tp heads; the
        # row-sharded wo leaves a partial sum — the layer's first of two
        # all-reduces restores the replicated residual stream
        with jax.named_scope("tp_psum"):
            y = jax.lax.psum(y, ctx.tp_axis)
    y = ctx.shard(y, "batch", "seq_res", "act_embed")
    return y, new_cache
