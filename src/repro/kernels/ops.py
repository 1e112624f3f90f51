"""Public kernel API: jit-friendly wrappers that dispatch between the pure
jnp reference paths, the scan-based blockwise implementations, and the
Pallas TPU kernels (compiled on TPU; ``interpret=True`` runs them on the
CPU, where the tests check them against the oracles).

  multi_head_attention : direct softmax / blockwise flash / Pallas flash
  expert_gemm          : batched per-expert GEMM (MoE)
  rwkv6_scan           : RWKV-6 WKV recurrence (chunked, remat-checkpointed)
  mamba_scan           : Mamba selective scan (chunked, remat-checkpointed)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_jnp import flash_attention


def multi_head_attention(q, k, v, *, causal: bool = True,
                         sm_scale: float | None = None,
                         window: int | None = None, kv_len=None, q_offset=0,
                         impl: str = "flash", block_q: int = 512,
                         block_kv: int = 1024, causal_skip: bool = False,
                         interpret: bool = False):
    """q: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D) -> (B,Sq,Hq,D)."""
    if impl == "direct":
        return ref.mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                                 window=window, kv_len=kv_len,
                                 q_offset=q_offset)
    if impl == "flash":
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_kv=block_kv,
                               window=window, kv_len=kv_len,
                               q_offset=q_offset, causal_skip=causal_skip)
    if impl == "pallas":
        from .flash_attention import pallas_flash_attention
        return pallas_flash_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, block_q=block_q,
            block_kv=block_kv, window=window, kv_len=kv_len,
            q_offset=q_offset, interpret=interpret)
    raise ValueError(f"unknown attention impl {impl!r}")


def paged_decode_attention(q, k_pool, v_pool, page_table, lengths, *,
                           sm_scale: float | None = None,
                           impl: str = "gather", interpret: bool = False):
    """Single-token decode attention against a paged KV pool.

    q: (B, 1, Hq, D); k_pool, v_pool: (P, Hkv, page_size, D) — the
    resident layout, head axis ahead of the page-token axis so one
    (page, head) tile is a contiguous block; page_table: (B, max_pages)
    int32 (page 0 = reserved null page); lengths: (B,) valid KV tokens
    (including the token just inserted).

      gather : materialize the per-slot linear view, masked softmax (the
               jnp oracle — what CPU runs)
      pallas : the TPU kernel walking the page table via scalar prefetch
    """
    if impl == "gather":
        return ref.paged_decode_reference(q, k_pool, v_pool, page_table,
                                          lengths, sm_scale=sm_scale)
    if impl == "pallas":
        from .decode_attention import pallas_paged_decode_attention
        return pallas_paged_decode_attention(q, k_pool, v_pool, page_table,
                                             lengths, sm_scale=sm_scale,
                                             interpret=interpret)
    raise ValueError(f"unknown paged decode impl {impl!r}")


def ragged_paged_attention(q, k_pool, v_pool, seg_page_table, q_start,
                           q_len, kv_len, *, max_q: int,
                           sm_scale: float | None = None,
                           impl: str = "gather", interpret: bool = False):
    """Token-packed mixed prefill+decode attention against a paged pool —
    the unified serving step's single attention dispatch.

    q: (T, Hq, D) packed queries; k_pool, v_pool: (P, Hkv, page_size, D)
    resident pools; seg_page_table: (S, max_pages) int32 per-segment page
    ids; q_start/q_len/kv_len: (S,) segment table (token offset, new
    tokens, total valid KV after insert); max_q: static q_len bound (the
    engine's chunk size).  Returns (T, Hq, D).

      gather : per-segment page gather + masked softmax (the jnp oracle)
      pallas : one kernel, grid (kv-head x segment, block of pages),
               scalar-prefetch segment + page tables steering the copies
    """
    if impl == "gather":
        return ref.ragged_paged_reference(q, k_pool, v_pool, seg_page_table,
                                          q_start, q_len, kv_len,
                                          max_q=max_q, sm_scale=sm_scale)
    if impl == "pallas":
        from .ragged_attention import pallas_ragged_paged_attention
        return pallas_ragged_paged_attention(
            q, k_pool, v_pool, seg_page_table, q_start, q_len, kv_len,
            max_q=max_q, sm_scale=sm_scale, interpret=interpret)
    raise ValueError(f"unknown ragged paged impl {impl!r}")


def expert_gemm(x, w, impl: str = "jnp", interpret: bool = False):
    """Batched per-expert GEMM: (E,C,D) @ (E,D,F) -> (E,C,F)."""
    if impl == "jnp":
        return jnp.einsum("ecd,edf->ecf", x, w,
                          preferred_element_type=jnp.float32).astype(x.dtype)
    if impl == "pallas":
        from .moe_gemm import pallas_expert_gemm
        return pallas_expert_gemm(x, w, interpret=interpret)
    raise ValueError(impl)


def _chunked_recurrence(ref_fn, state, time_args, other_args, chunk: int,
                        time_axis: int = 1):
    """Run a sequential recurrence in remat-checkpointed chunks.

    Backward memory: one state per chunk boundary + per-step residuals of a
    single chunk (recomputed), instead of per-step residuals of the whole
    sequence.
    """
    t = time_args[0].shape[time_axis]
    if t <= chunk:
        return ref_fn(*time_args, *other_args, state)
    pad = (-t) % chunk
    if pad:
        time_args = tuple(
            jnp.pad(a, [(0, pad) if i == time_axis else (0, 0)
                        for i in range(a.ndim)]) for a in time_args)
    nc = (t + pad) // chunk

    def split(a):
        shp = a.shape
        a = a.reshape(shp[:time_axis] + (nc, chunk) + shp[time_axis + 1:])
        return jnp.moveaxis(a, time_axis, 0)

    xs = tuple(split(a) for a in time_args)

    @jax.checkpoint
    def body(s, chunk_args):
        out, s = ref_fn(*chunk_args, *other_args, s)
        return s, out

    final, outs = jax.lax.scan(body, state, xs)
    # outs: (nc, ..., chunk, ...) -> re-interleave the chunk axis in place
    out = jnp.moveaxis(outs, 0, time_axis)
    shp = out.shape
    out = out.reshape(shp[:time_axis] + (nc * chunk,) + shp[time_axis + 2:])
    if pad:
        out = jax.lax.slice_in_dim(out, 0, t, axis=time_axis)
    return out, final


def rwkv6_scan(r, k, v, w, u, state, *, chunk: int = 128,
               impl: str = "chunked", interpret: bool = False):
    """RWKV-6 WKV: r,k,v,w (B,T,H,N), u (H,N), state (B,H,N,N)."""
    if impl == "pallas":
        from .ssm_scan import pallas_rwkv6_scan
        return pallas_rwkv6_scan(r, k, v, w, u, state, chunk=chunk,
                                 interpret=interpret)
    if impl == "ref" or r.shape[1] <= chunk:
        return ref.rwkv6_reference(r, k, v, w, u, state)
    return _chunked_recurrence(ref.rwkv6_reference, state, (r, k, v, w),
                               (u,), chunk)


def mamba_scan(x, dt, a, b, c, d, state, *, chunk: int = 128,
               impl: str = "chunked"):
    """Mamba selective scan: x,dt (B,T,Di); a (Di,N); b,c (B,T,N); d (Di,);
    state (B,Di,N)."""
    if impl == "ref" or x.shape[1] <= chunk:
        return ref.mamba_scan_reference(x, dt, a, b, c, d, state)

    def ref_reordered(x_, dt_, b_, c_, a_, d_, s_):
        return ref.mamba_scan_reference(x_, dt_, a_, b_, c_, d_, s_)

    return _chunked_recurrence(ref_reordered, state, (x, dt, b, c), (a, d),
                               chunk)
