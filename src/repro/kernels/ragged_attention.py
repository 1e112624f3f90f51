"""Pallas TPU ragged paged attention: one dispatch for mixed prefill+decode.

The unified token-packed serving step (paper §Chunked serving; the
"piggybacking" of prefill chunks onto decode batches) packs the decode
tokens of every active slot and the current prefill chunk of every
in-flight prompt into one ragged ``(T, Hq, D)`` query batch.  Each
*segment* of that batch (one decode slot or one prefill chunk) attends
against exactly the KV pages its request owns:

  grid = (Hkv * S, max_pages) — kv heads x S segments outer, the segment's
  page walk inner.  The segment table (``q_start``/``q_len``/``kv_len``)
  and the per-segment page table ride in as scalar-prefetch operands, so
  the K/V BlockSpec index maps steer each grid step's DMA to the page the
  segment owns before the body runs; the body is the same online-softmax
  combine as the decode kernels, with two extra mask terms:

    * causal masking *within* the segment — a prefill chunk's query at
      in-chunk offset i sits at global position kv_len - q_len + i and may
      only see keys at positions <= that (decode degenerates to the usual
      "see everything valid" with q_len == 1; a K+1-token speculative
      *verify* segment — one committed token followed by K draft
      proposals — is exactly this rule at q_len = K+1, so batched
      draft-token verification needs no kernel change, only the
      fixed-stride packing in :class:`repro.serving.PackedSpeculator`),
    * ragged row masking — rows past ``q_len`` (the fixed-width query tile
      of a shorter segment, or an inactive segment with q_len == 0)
      contribute nothing and produce zeros.

  HBM traffic stays K + V exactly: pages wholly beyond ``kv_len`` are
  skipped, and no per-request linearization is ever materialized.

K/V pools use the resident ``(P, Hkv, page_size, D)`` layout (head axis
ahead of the page-token axis), so one (page, head) tile is a contiguous
block and no transpose happens per call.  Queries go to the kernel
head-major, ``(Hkv, T, G, D)``: the q index map picks the kv head's query
group, and the body's per-segment row slice lands on an untiled leading
axis.  (Slicing query heads inside the kernel put a dynamic offset on the
sublane axis, which Mosaic refuses.)

Runs compiled on TPU; validated against
:func:`repro.kernels.ref.ragged_paged_reference` in interpret mode (tests
+ property tests over random packings) and compiled for a described v5e
chip in ``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_jnp import NEG_INF
from .ref import ragged_pack_indices


def _ragged_kernel(pt_ref, qs_ref, ql_ref, kl_ref, q_ref, k_ref, v_ref,
                   o_ref, acc_ref, m_ref, l_ref, *, sm_scale: float,
                   page_size: int, n_pages: int, n_segs: int, g: int,
                   max_q: int):
    """Grid (Hkv * S, max_pages).  ``pt_ref`` (S, max_pages) and the
    (S,) segment table ``qs/ql/kl`` are scalar-prefetch operands; the K/V
    index maps already walked them, so the body only masks and combines.
    ``q_ref`` is the (1, T + max_q, G, D) head-major block of this step's
    kv head, picked by its index map."""
    hs, j = pl.program_id(0), pl.program_id(1)
    s = hs % n_segs
    qs = qs_ref[s]
    ql = ql_ref[s]
    kl = kl_ref[s]
    q2 = max_q * g
    # float32 operands contract at float32 (what a float32 reference
    # compares against); bf16 ones take the MXU's native pass
    prec = jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body():
        d = q_ref.shape[-1]
        # the segment's fixed-width query tile: (max_q, G, D) rows past
        # q_len are masked below
        qt = q_ref[0, pl.ds(qs, max_q), :, :]
        qf = qt.reshape(q2, d).astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)  # (page_size, D)
        sc = jax.lax.dot_general(qf, k, (((1,), (1,)), ((), ())),
                                 precision=prec,
                                 preferred_element_type=jnp.float32)
        sc = sc * sm_scale  # (q2, page_size)
        # row r of the flattened tile is query i = r // g of the segment,
        # at global position kv_start + i
        row = jax.lax.broadcasted_iota(jnp.int32, (q2, 1), 0) // g
        qpos = (kl - ql) + row  # (q2, 1)
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = (kpos <= qpos) & (kpos < kl) & (row < ql)
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[:, None]) * valid
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + p.sum(axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())), precision=prec,
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    # pages wholly beyond the segment's valid prefix (and inactive
    # segments) are skipped — their table entries are the null page anyway
    pl.when((j * page_size < kl) & (ql > 0))(body)

    @pl.when(j == n_pages - 1)
    def _finish():
        d = q_ref.shape[-1]
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]) \
            .reshape(max_q, g, d).astype(o_ref.dtype)


def pallas_ragged_paged_attention(q, k_pool, v_pool, seg_page_table, q_start,
                                  q_len, kv_len, *, max_q: int,
                                  sm_scale: float | None = None,
                                  interpret: bool = False) -> jax.Array:
    """q: (T, Hq, D) token-packed queries; k_pool, v_pool: the resident
    (P, Hkv, page_size, D) pools; seg_page_table: (S, max_pages) int32 page
    ids per segment (0 = reserved null page); q_start: (S,) nondecreasing
    token offsets of each segment's queries in ``q``; q_len: (S,) query
    tokens per segment (0 = inactive); kv_len: (S,) total valid KV tokens
    per segment *including* this step's q_len new tokens; max_q: static
    upper bound on q_len (the engine's chunk size).

    Returns (T, Hq, D) packed outputs.  Equivalent to, per segment,
    gathering its pages into a linear view and running causal attention
    with kv_len masking and q_offset = kv_len - q_len — but the gather
    never materializes (scalar-prefetch page walk) and every segment rides
    the same dispatch.  Rows belonging to no live segment (packing gaps)
    return unspecified values; callers mask by segment.
    """
    from jax.experimental.pallas import tpu as pltpu

    t, hq, d = q.shape
    n_pool, hkv, ps, _ = k_pool.shape
    s_count, max_pages = seg_page_table.shape
    g = hq // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)

    # head-major (Hkv, T + max_q, G, D): the index map picks the kv head's
    # query group, so the segment's dynamic row offset falls on an untiled
    # leading axis and never on the tiled (G, D) plane.  The token axis is
    # padded so a fixed-width tile starting at any q_start stays in bounds
    # (padding rows are masked by q_len).
    qh = jnp.pad(q, ((0, max_q), (0, 0), (0, 0))).reshape(t + max_q, hkv, g, d)
    qh = jnp.moveaxis(qh, 1, 0)

    kernel = functools.partial(_ragged_kernel, sm_scale=scale, page_size=ps,
                               n_pages=max_pages, n_segs=s_count, g=g,
                               max_q=max_q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # seg_page_table, q_start, q_len, kv_len
        # kv head outer, segment inner: one head's q block stays resident
        # across every segment
        grid=(hkv * s_count, max_pages),
        in_specs=[
            # one kv head's whole packed q group rides in VMEM (T is one
            # step's tokens — max_slots + prefill_rows * chunk — not a
            # context length)
            pl.BlockSpec((1, t + max_q, g, d),
                         lambda hs, j, pt, qs, ql, kl: (hs // s_count,
                                                        0, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda hs, j, pt, qs, ql, kl: (pt[hs % s_count, j],
                                                        hs // s_count, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda hs, j, pt, qs, ql, kl: (pt[hs % s_count, j],
                                                        hs // s_count, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, max_q, g, d),
                               lambda hs, j, pt, qs, ql, kl: (hs, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((max_q * g, d), jnp.float32),
            pltpu.VMEM((max_q * g,), jnp.float32),
            pltpu.VMEM((max_q * g,), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="ragged_paged_attention",
        out_shape=jax.ShapeDtypeStruct((hkv * s_count, max_q, g, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(seg_page_table, jnp.int32),
      jnp.asarray(q_start, jnp.int32), jnp.asarray(q_len, jnp.int32),
      jnp.asarray(kv_len, jnp.int32), qh, k_pool, v_pool)
    # (Hkv*S, max_q, G, D) -> segment-major (S, max_q, Hq, D) -> re-pack
    o = o.reshape(hkv, s_count, max_q, g, d)
    o = jnp.moveaxis(o, 0, 2).reshape(s_count * max_q, hq, d)
    idx = ragged_pack_indices(q_start, q_len, t, max_q)
    return jnp.take(o, idx, axis=0)
