"""Pallas TPU ragged paged attention: one dispatch for mixed prefill+decode.

The unified token-packed serving step (paper §Chunked serving; the
"piggybacking" of prefill chunks onto decode batches) packs the decode
tokens of every active slot and the current prefill chunk of every
in-flight prompt into one ragged ``(T, Hq, D)`` query batch.  Each
*segment* of that batch (one decode slot or one prefill chunk) attends
against exactly the KV pages its request owns:

  grid = (Hkv * S, cdiv(max_pages, pages_per_block)) — kv heads x S
  segments outer, the segment's walk over *blocks* of pages inner.  The
  segment table (``q_start``/``q_len``/``kv_len``) and the per-segment page
  table ride in as scalar-prefetch operands.  The pools stay in HBM: each
  grid step copies its block's pages (one ``(page_size, D)`` tile of K and
  of V per page, read from the page table) into double-buffered VMEM
  scratch, and starts the copies of the next live block — of this
  segment, or the first block of the next live segment — before it
  computes, so the DMA overlaps the compute.  The body is the same
  online-softmax combine as the decode kernels over a
  ``(max_q * G, pages_per_block * page_size)`` score tile, with two extra
  mask terms:

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

  HBM traffic stays K + V exactly: only pages below ``cdiv(kv_len,
  page_size)`` are copied (never the null page past them), blocks wholly
  beyond ``kv_len`` and inactive segments copy and compute nothing, and
  no per-request linearization is ever materialized.

``pages_per_block`` (:func:`pages_per_block`) is a function of the shapes
alone: about 512 keys a block, fewer where the float32 score tile of a
wide query tile (a prefill chunk) would pass ``SCORE_TILE_BYTES``.

Mosaic slices a pool tile in HBM only by whole 128-lane rows, so a head
narrower than 128 (qwen1.5-0.5b's 64) cannot be copied by the kernel
itself: there each page of a block is one input of the grid's pipeline,
whose index map reads the page table (and, past the segment's pages,
stays on the page the input last held, so dead pages copy nothing).  The
walk, the masks and the combine are the same.

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
from jax.experimental.pallas import tpu as pltpu

from .flash_jnp import NEG_INF
from .ref import ragged_pack_indices

BLOCK_KEYS = 512  # keys a grid step aims to attend
SCORE_TILE_BYTES = 2 << 20  # the float32 (max_q * G, block keys) tile
_MIB = 1 << 20


def pages_per_block(page_size: int, max_pages: int, rows: int) -> int:
    """KV pages one grid step attends: ``BLOCK_KEYS`` keys, cut so the
    float32 score tile of ``rows`` (= max_q * G) query rows stays within
    ``SCORE_TILE_BYTES``; a power of two (lane-aligned blocks), in
    ``[1, max_pages]``."""
    keys = min(BLOCK_KEYS, SCORE_TILE_BYTES // (4 * rows))
    pages = max(1, keys // page_size)
    return min(1 << (pages.bit_length() - 1), max_pages)


def vmem_limit_bytes(t: int, max_q: int, g: int, head_dim: int,
                     itemsize: int, block_keys: int) -> int:
    """Scoped VMEM the kernel asks for: the double-buffered q and output
    blocks (G padded to the dtype's sublanes), the K/V scratch, the
    accumulator and four score-tile-sized temporaries, plus 4 MiB."""
    def up(x, m):
        return -(-x // m) * m
    lanes = up(head_dim, 128)
    gp = up(g, 32 // itemsize)
    rows = up(max_q * g, 8)
    blocks = 2 * (t + 2 * max_q) * gp * lanes * itemsize
    kv = 4 * block_keys * lanes * itemsize
    state = rows * lanes * (4 + itemsize) + 2 * rows * 4
    tiles = 4 * rows * up(block_keys, 128) * 4
    return max(16 * _MIB, blocks + kv + state + tiles + 4 * _MIB)


def _manual_copies(head_dim: int) -> bool:
    """Whether the kernel copies its pages itself (whole-lane heads)."""
    return head_dim % 128 == 0


def _ragged_kernel(pt_ref, qs_ref, ql_ref, kl_ref, nx_ref, q_ref, *refs,
                   sm_scale: float, page_size: int, max_pages: int, ppb: int,
                   n_blocks: int, n_segs: int, n_hs: int, g: int, max_q: int,
                   manual: bool):
    """Grid (Hkv * S, n_blocks).  ``pt_ref`` (S, max_pages) and the (S,)
    segment table ``qs/ql/kl`` are scalar-prefetch operands, as is
    ``nx_ref`` (S,): from a segment's row ``hs``, ``hs + nx[s]`` is the
    next row with a live segment.  ``q_ref`` is the (1, T + max_q, G, D)
    head-major block of this step's kv head, picked by its index map.

    ``manual``: ``refs`` opens with the K and V pools, left in HBM, and
    the step copies its block's pages itself into the double-buffered
    ``k_buf``/``v_buf``; ``slot_ref`` (SMEM) holds the buffer the current
    block lands in, and whether a live block has been copied yet.
    Otherwise ``refs`` opens with ``ppb`` K and ``ppb`` V page blocks,
    one page each, that the grid's pipeline fetched by page table."""
    if manual:
        (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, q_scr, acc_ref, m_ref,
         l_ref, slot_ref) = refs
    else:
        k_pages, v_pages = refs[:ppb], refs[ppb:2 * ppb]
        o_ref, q_scr, acc_ref, m_ref, l_ref = refs[2 * ppb:]
    hs, j = pl.program_id(0), pl.program_id(1)
    s, h = hs % n_segs, hs // n_segs
    qs = qs_ref[s]
    ql = ql_ref[s]
    kl = kl_ref[s]
    q2 = max_q * g
    bk = ppb * page_size
    # float32 operands contract at float32 (what a float32 reference
    # compares against); bf16 ones take the MXU's native pass
    prec = jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None

    def copies(seg, head, blk, slot):
        """Apply ``fn`` (start or wait) to the K and V page copies of
        block ``blk`` of ``seg`` at ``head``: pages below cdiv(kv_len,
        page_size) only."""
        first = blk * ppb
        n = jnp.minimum(jnp.minimum(ppb, max_pages - first),
                        pl.cdiv(kl_ref[seg], page_size) - first)

        def each(fn):
            def one(i, carry):
                page = pt_ref[seg, first + i]
                for c, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    fn(pltpu.make_async_copy(hbm.at[page, head],
                                             buf.at[slot, i],
                                             sems.at[c, slot]))
                return carry
            jax.lax.fori_loop(0, n, one, 0)
        return each

    if manual:
        @pl.when((hs == 0) & (j == 0))
        def _start_call():
            slot_ref[0] = 0
            slot_ref[1] = 1  # no live block copied yet

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def start_copies():
        """Copy this block if no step before did, then start the next
        live block's copies (this segment's next, else block 0 of the
        next live row) into the other buffer."""
        slot = slot_ref[0]

        @pl.when(slot_ref[1] == 1)
        def _first():
            copies(s, h, j, slot)(lambda c: c.start())
            slot_ref[1] = 0

        more = (j + 1) * bk < kl

        @pl.when(more)
        def _next_block():
            copies(s, h, j + 1, 1 - slot)(lambda c: c.start())

        nxt = hs + nx_ref[s]

        @pl.when(jnp.logical_not(more) & (nxt < n_hs))
        def _next_row():
            copies(nxt % n_segs, nxt // n_segs, 0, 1 - slot)(
                lambda c: c.start())

    def body():
        d = q_ref.shape[-1]
        if manual:
            start_copies()

        @pl.when(j == 0)
        def _load_q():
            # the segment's fixed-width query tile: (max_q, G, D); rows
            # past q_len are masked below
            q_scr[...] = q_ref[0, pl.ds(qs, max_q), :, :].reshape(q2, d)

        if manual:
            slot = slot_ref[0]
            copies(s, h, j, slot)(lambda c: c.wait())
            k = k_buf[slot].reshape(bk, d)
            v = v_buf[slot].reshape(bk, d)
            slot_ref[0] = 1 - slot
        else:
            k = jnp.concatenate([r[0, 0] for r in k_pages], axis=0)
            v = jnp.concatenate([r[0, 0] for r in v_pages], axis=0)
        sc = jax.lax.dot_general(q_scr[...], k, (((1,), (1,)), ((), ())),
                                 precision=prec,
                                 preferred_element_type=jnp.float32)
        sc = sc * sm_scale  # (q2, bk)
        # row r of the flattened tile is query i = r // g of the segment,
        # at global position kv_start + i
        row = jax.lax.broadcasted_iota(jnp.int32, (q2, 1), 0) // g
        qpos = (kl - ql) + row  # (q2, 1)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = (kpos <= qpos) & (kpos < kl) & (row < ql)
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1))
        p = jnp.where(valid, jnp.exp(sc - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + p.sum(axis=-1)
        # rows past the segment's pages hold whatever the buffer held
        # before (not finite, for all we know): zero them, as p is there
        kcol = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v = jnp.where(kcol < kl, v.astype(jnp.float32), 0.0)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())), precision=prec,
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    # blocks wholly beyond the segment's valid prefix, and inactive
    # segments, copy and compute nothing
    pl.when((j * bk < kl) & (ql > 0))(body)

    @pl.when(j == n_blocks - 1)
    def _finish():
        d = q_ref.shape[-1]
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]) \
            .reshape(max_q, g, d).astype(o_ref.dtype)


def _page_spec(i, ppb, page_size, n_segs, d):
    """The grid pipeline's K or V block for input ``i`` of a block: the
    segment's page ``j * ppb + i``; past the segment's pages it stays on
    the page the input last held, so dead pages and dead blocks copy
    nothing (and an input no page of the segment reaches re-reads its
    last page)."""
    def imap(hs, j, pt, qs, ql, kl, nx):
        s = hs % n_segs
        n = jnp.maximum(pl.cdiv(kl[s], page_size), 1)
        last = jnp.where(i < n, i + (n - 1 - i) // ppb * ppb, n - 1)
        return pt[s, jnp.minimum(j * ppb + i, last)], hs // n_segs, 0, 0
    return pl.BlockSpec((1, 1, page_size, d), imap)


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
    never materializes (page copies steered by the page table) and every
    segment rides the same dispatch.  Rows belonging to no live segment
    (packing gaps) return unspecified values; callers mask by segment.
    """
    t, hq, d = q.shape
    n_pool, hkv, ps, _ = k_pool.shape
    s_count, max_pages = seg_page_table.shape
    g = hq // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    ppb = pages_per_block(ps, max_pages, max_q * g)
    n_blocks = pl.cdiv(max_pages, ppb)
    bk = ppb * ps

    # head-major (Hkv, T + max_q, G, D): the index map picks the kv head's
    # query group, so the segment's dynamic row offset falls on an untiled
    # leading axis and never on the tiled (G, D) plane.  The token axis is
    # padded so a fixed-width tile starting at any q_start stays in bounds
    # (padding rows are masked by q_len).
    qh = jnp.pad(q, ((0, max_q), (0, 0), (0, 0))).reshape(t + max_q, hkv, g, d)
    qh = jnp.moveaxis(qh, 1, 0)

    # nx[s]: rows from segment s's row to the next row whose segment is
    # live (rows run kv head outer, segment inner, so within S of it)
    q_len = jnp.asarray(q_len, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    live = (q_len > 0) & (kv_len > 0)
    ahead = jnp.arange(1, s_count + 1, dtype=jnp.int32)
    later = (jnp.arange(s_count, dtype=jnp.int32)[:, None] + ahead) % s_count
    nx = ahead[jnp.argmax(live[later], axis=1)]

    # Mosaic slices an HBM tile by whole 128-lane rows only: a narrower
    # head's pages come through the grid's pipeline, one input a page
    manual = _manual_copies(d)
    if manual:
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        kv_args = (k_pool, v_pool)
        buffers = [pltpu.VMEM((2, ppb, ps, d), k_pool.dtype),
                   pltpu.VMEM((2, ppb, ps, d), v_pool.dtype),
                   pltpu.SemaphoreType.DMA((2, 2))]  # (K or V, buffer)
    else:
        kv_specs = [_page_spec(i, ppb, ps, s_count, d)
                    for i in range(ppb)] * 2
        kv_args = (k_pool,) * ppb + (v_pool,) * ppb
        buffers = []
    kernel = functools.partial(_ragged_kernel, sm_scale=scale, page_size=ps,
                               max_pages=max_pages, ppb=ppb,
                               n_blocks=n_blocks, n_segs=s_count,
                               n_hs=hkv * s_count, g=g, max_q=max_q,
                               manual=manual)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # seg_page_table, q_start, q_len, kv_len, nx
        num_scalar_prefetch=5,
        # kv head outer, segment inner: one head's q block stays resident
        # across every segment
        grid=(hkv * s_count, n_blocks),
        in_specs=[
            # one kv head's whole packed q group rides in VMEM (T is one
            # step's tokens — max_slots + prefill_rows * chunk — not a
            # context length)
            pl.BlockSpec((1, t + max_q, g, d),
                         lambda hs, j, *_: (hs // s_count, 0, 0, 0)),
            *kv_specs,
        ],
        out_specs=pl.BlockSpec((1, max_q, g, d),
                               lambda hs, j, *_: (hs, 0, 0, 0)),
        scratch_shapes=buffers + [
            pltpu.VMEM((max_q * g, d), q.dtype),
            pltpu.VMEM((max_q * g, d), jnp.float32),
            pltpu.VMEM((max_q * g,), jnp.float32),
            pltpu.VMEM((max_q * g,), jnp.float32),
        ] + ([pltpu.SMEM((2,), jnp.int32)] if manual else []),
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="ragged_paged_attention",
        out_shape=jax.ShapeDtypeStruct((hkv * s_count, max_q, g, d), q.dtype),
        # a step starts the copies of the next live step: the grid runs
        # in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(t, max_q, g, d, itemsize, bk)),
        interpret=interpret,
    )(jnp.asarray(seg_page_table, jnp.int32),
      jnp.asarray(q_start, jnp.int32), q_len, kv_len, nx, qh, *kv_args)
    # (Hkv*S, max_q, G, D) -> segment-major (S, max_q, Hq, D) -> re-pack
    o = o.reshape(hkv, s_count, max_q, g, d)
    o = jnp.moveaxis(o, 0, 2).reshape(s_count * max_q, hq, d)
    idx = ragged_pack_indices(q_start, q_len, t, max_q)
    return jnp.take(o, idx, axis=0)
