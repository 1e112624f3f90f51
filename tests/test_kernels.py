"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret=True) vs the
pure-jnp oracles in ref.py (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.decode_attention import pallas_decode_attention
from repro.kernels.flash_attention import pallas_flash_attention
from repro.kernels.moe_gemm import pallas_expert_gemm
from repro.kernels.ssm_scan import pallas_rwkv6_scan


def t(shape, k, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.key(k), shape, jnp.float32)
            * scale).astype(dtype)


TOL = {jnp.float32: 2e-4, jnp.bfloat16: 4e-2}


# ---------------------------------------------------------------------------
# flash attention (jnp blockwise + pallas)
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Sq, Skv, Hq, Hkv, D, causal, window)
    (2, 64, 64, 4, 4, 16, True, None),
    (2, 64, 64, 4, 2, 16, True, None),
    (1, 128, 128, 8, 2, 32, False, None),
    (2, 64, 64, 4, 4, 16, True, 24),
    (1, 96, 96, 2, 1, 64, True, None),
    (3, 32, 32, 6, 3, 8, True, None),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_jnp_vs_oracle(case, dtype):
    B, Sq, Skv, Hq, Hkv, D, causal, win = case
    q, k, v = (t((B, Sq, Hq, D), 1, dtype), t((B, Skv, Hkv, D), 2, dtype),
               t((B, Skv, Hkv, D), 3, dtype))
    want = ref.mha_reference(q, k, v, causal=causal, window=win)
    got = kops.multi_head_attention(q, k, v, causal=causal, window=win,
                                    impl="flash", block_q=16, block_kv=32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FLASH_CASES[:4])
def test_flash_pallas_vs_oracle(case, dtype):
    B, Sq, Skv, Hq, Hkv, D, causal, win = case
    q, k, v = (t((B, Sq, Hq, D), 1, dtype), t((B, Skv, Hkv, D), 2, dtype),
               t((B, Skv, Hkv, D), 3, dtype))
    want = ref.mha_reference(q, k, v, causal=causal, window=win)
    got = pallas_flash_attention(q, k, v, causal=causal, window=win,
                                 block_q=32, block_kv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_gradients_match_direct():
    B, S, Hq, Hkv, D = 2, 64, 4, 2, 16
    q, k, v = t((B, S, Hq, D), 1), t((B, S, Hkv, D), 2), t((B, S, Hkv, D), 3)

    def loss(impl):
        def f(q, k, v):
            o = kops.multi_head_attention(q, k, v, impl=impl, block_q=16,
                                          block_kv=16)
            return jnp.sum(jnp.sin(o))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(loss("direct"), loss("flash")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_chunked_offsets():
    """Chunked prefill: per-request q_offset + kv_len masks."""
    B, Skv, Hq, Hkv, D = 2, 96, 4, 2, 16
    q = t((B, 48, Hq, D), 1)
    k, v = t((B, Skv, Hkv, D), 2), t((B, Skv, Hkv, D), 3)
    kv_len = jnp.array([80, 60])
    q_off = jnp.array([32, 12])
    want = ref.mha_reference(q, k, v, causal=True, kv_len=kv_len,
                             q_offset=q_off)
    got = kops.multi_head_attention(q, k, v, causal=True, kv_len=kv_len,
                                    q_offset=q_off, impl="flash",
                                    block_q=16, block_kv=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_flash_causal_skip_matches():
    B, S, H, D = 1, 128, 2, 16
    q, k, v = t((B, S, H, D), 1), t((B, S, H, D), 2), t((B, S, H, D), 3)
    base = kops.multi_head_attention(q, k, v, impl="flash", block_q=32,
                                     block_kv=32)
    skip = kops.multi_head_attention(q, k, v, impl="flash", block_q=32,
                                     block_kv=32, causal_skip=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(skip), atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,Hq,Hkv,D,bk", [
    (3, 96, 8, 2, 16, 32), (1, 64, 4, 4, 32, 16), (2, 128, 16, 8, 8, 64),
])
def test_decode_kernel_vs_oracle(B, T, Hq, Hkv, D, bk, dtype):
    q = t((B, 1, Hq, D), 1, dtype)
    k, v = t((B, T, Hkv, D), 2, dtype), t((B, T, Hkv, D), 3, dtype)
    lengths = jnp.arange(1, B + 1) * (T // (B + 1)) + 1
    want = ref.mha_reference(q, k, v, causal=False, kv_len=lengths,
                             q_offset=lengths - 1)
    got = pallas_decode_attention(q, k, v, lengths=lengths, block_kv=bk,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# paged decode attention kernel (page-table-walking grid)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,D,P,ps,mp", [
    (3, 8, 2, 16, 12, 8, 4), (1, 4, 4, 32, 5, 16, 2), (2, 16, 8, 8, 9, 4, 8),
])
def test_paged_decode_kernel_vs_gather_oracle(B, Hq, Hkv, D, P, ps, mp,
                                              dtype):
    """The scalar-prefetch page walk must equal the materialized gather +
    masked softmax, across partial last pages and null-page padding."""
    rng = np.random.default_rng(0)
    q = t((B, 1, Hq, D), 1, dtype)
    # resident pool layout: (P, Hkv, page_size, D)
    kp, vp = t((P, Hkv, ps, D), 2, dtype), t((P, Hkv, ps, D), 3, dtype)
    # each slot owns a distinct page run; unused tail entries -> null page 0
    pt = np.zeros((B, mp), np.int32)
    free = list(range(1, P))
    lengths = []
    for b in range(B):
        n_tok = int(rng.integers(1, mp * ps))
        n_pages = -(-n_tok // ps)
        n_pages = min(n_pages, len(free))
        for i in range(n_pages):
            pt[b, i] = free.pop()
        lengths.append(min(n_tok, n_pages * ps))
    pt, lengths = jnp.asarray(pt), jnp.asarray(lengths, jnp.int32)
    want = kops.paged_decode_attention(q, kp, vp, pt, lengths,
                                       impl="gather")
    got = kops.paged_decode_attention(q, kp, vp, pt, lengths, impl="pallas",
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_decode_gather_matches_dense_reference():
    """Linearizing a paged pool through its page table reproduces dense
    decode attention on the equivalent left-aligned cache."""
    B, T, Hq, Hkv, D, ps = 2, 32, 4, 2, 16, 8
    q = t((B, 1, Hq, D), 1)
    k, v = t((B, T, Hkv, D), 2), t((B, T, Hkv, D), 3)
    lengths = jnp.asarray([13, 27], jnp.int32)
    # build the pool by slicing the dense cache into pages (resident
    # layout: head axis ahead of the page-token axis)
    mp = T // ps
    kp = [jnp.zeros((Hkv, ps, D))]
    vp = [jnp.zeros((Hkv, ps, D))]
    pt = np.zeros((B, mp), np.int32)
    for b in range(B):
        for p in range(mp):
            pt[b, p] = len(kp)
            kp.append(jnp.swapaxes(k[b, p * ps:(p + 1) * ps], 0, 1))
            vp.append(jnp.swapaxes(v[b, p * ps:(p + 1) * ps], 0, 1))
    kp, vp = jnp.stack(kp), jnp.stack(vp)
    want = ref.mha_reference(q, k, v, causal=False, kv_len=lengths,
                             q_offset=lengths - 1)
    got = kops.paged_decode_attention(q, kp, vp, jnp.asarray(pt), lengths,
                                      impl="gather")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# ragged paged attention kernel (the unified mixed prefill+decode dispatch)
# ---------------------------------------------------------------------------

def _ragged_case(rng, segs, Hq, Hkv, D, ps, mp, max_q, dtype=jnp.float32):
    """Build a packed case from (q_len, kv_len) segment tuples.  Segments
    pack back-to-back; every segment gets a distinct page run."""
    S = len(segs)
    P = 1 + sum(-(-kv // ps) for _, kv in segs) + 1
    kp = t((P, Hkv, ps, D), 11, dtype)
    vp = t((P, Hkv, ps, D), 12, dtype)
    pt = np.zeros((S, mp), np.int32)
    free = list(range(1, P))
    q_start, q_len, kv_len = [], [], []
    off = 0
    for ql, kl in segs:
        q_start.append(off)
        q_len.append(ql)
        kv_len.append(kl)
        for i in range(-(-kl // ps)):
            pt[len(q_start) - 1, i] = free.pop(0)
        off += ql
    T = max(off, 1)
    q = t((T, Hq, D), 13, dtype)
    return (q, kp, vp, jnp.asarray(pt), jnp.asarray(q_start, jnp.int32),
            jnp.asarray(q_len, jnp.int32), jnp.asarray(kv_len, jnp.int32))


def _ragged_valid_rows(q_start, q_len, T):
    valid = np.zeros((T,), bool)
    for s, l in zip(np.asarray(q_start), np.asarray(q_len)):
        valid[s:s + l] = True
    return valid


# (Hq, Hkv, D, page_size, max_pages, max_q)
RAGGED_GEOM = (4, 2, 16, 4, 6, 8)
# 32-token pages, 40 a segment: pages_per_block gives 16-page blocks of 512
# keys, so three blocks, the last one 8 pages (40 is no multiple of 16)
BLOCK_GEOM = (4, 2, 16, 32, 40, 8)
# the same walk at G = 1 (as many query as kv heads: one shard of a
# multi-head model at tp > 1), one query row per kv head and decode segment
MHA_BLOCK_GEOM = (2, 2, 16, 32, 40, 8)
BLOCK_CASES = {
    # kv_len exactly on a block boundary, one token past it, below a block
    "on-boundary": [(1, 512), (1, 1024)],
    "one-past": [(1, 513), (1, 1025)],
    "below-one-block": [(1, 100), (1, 511)],
    # the last, partial block of a full segment
    "ragged-tail": [(1, 1280), (1, 1100)],
    "inactive-between": [(1, 700), (0, 0), (1, 900)],
    # a K+1 = 5 token speculative verify segment straddling a boundary
    "verify": [(5, 514), (1, 30)],
    # prefill chunks whose causal edge falls inside a block, and one that
    # straddles a block boundary
    "prefill-causal-edge": [(8, 520), (8, 516), (3, 1030)],
}


@pytest.mark.parametrize("segs,geom,copies", [
    # mixed: two decode slots, an inactive segment, two prefill chunks
    pytest.param([(1, 7), (1, 13), (0, 0), (8, 8), (5, 11)], RAGGED_GEOM,
                 None, id="segs0"),
    # decode-only packing (every segment one token)
    pytest.param([(1, 5), (1, 9), (1, 16), (1, 1)], RAGGED_GEOM, None,
                 id="segs1"),
    # empty-prefill: idle rows ride along as q_len == 0 segments
    pytest.param([(1, 6), (0, 0), (0, 0)], RAGGED_GEOM, None, id="segs2"),
    # prefill-only, partial last pages
    pytest.param([(7, 7), (3, 15)], RAGGED_GEOM, None, id="segs3"),
    # the multi-page block walk, both ways pages reach the kernel: its own
    # double-buffered copies, and the grid pipeline's page inputs
    *[pytest.param(segs, BLOCK_GEOM, copies, id=f"{copies}-{name}")
      for copies in ("manual", "pipelined")
      for name, segs in BLOCK_CASES.items()],
    *[pytest.param(segs, MHA_BLOCK_GEOM, "manual", id=f"manual-mha-{name}")
      for name, segs in BLOCK_CASES.items()],
])
def test_ragged_paged_kernel_vs_gather_oracle(monkeypatch, segs, geom,
                                              copies):
    """One ragged dispatch over mixed decode + prefill segments must equal
    the per-segment gather + masked softmax oracle, including causal
    masking within prefill chunks and inactive segments."""
    from repro.kernels import ragged_attention
    if copies is not None:
        monkeypatch.setattr(ragged_attention, "_manual_copies",
                            lambda d: copies == "manual")
    rng = np.random.default_rng(0)
    Hq, Hkv, D, ps, mp, max_q = geom
    args = _ragged_case(rng, segs, Hq, Hkv, D, ps, mp, max_q)
    if geom in (BLOCK_GEOM, MHA_BLOCK_GEOM):
        ppb = ragged_attention.pages_per_block(ps, mp, max_q * Hq // Hkv)
        assert -(-mp // ppb) >= 3 and mp % ppb
    want = kops.ragged_paged_attention(*args, max_q=max_q, impl="gather")
    got = kops.ragged_paged_attention(*args, max_q=max_q, impl="pallas",
                                      interpret=True)
    valid = _ragged_valid_rows(args[4], args[5], args[0].shape[0])
    np.testing.assert_allclose(np.asarray(got, np.float32)[valid],
                               np.asarray(want, np.float32)[valid],
                               atol=2e-6, rtol=2e-6)


def test_ragged_pages_per_block_from_shapes():
    """The block size is a function of the shapes alone, within
    [1, max_pages]; the decode sub-call gets 512 keys at page size 16, and
    the float32 score tile of the prefill (max_q 256) and decode (max_q 1)
    sub-calls at G = 6, D = 128 stays within the VMEM the kernel asks for."""
    import inspect
    from repro.kernels import ragged_attention as ra
    assert list(inspect.signature(ra.pages_per_block).parameters) == [
        "page_size", "max_pages", "rows"]
    for ps in (1, 4, 16, 32, 128):
        for mp in (1, 3, 40, 256, 1000):
            for rows in (1, 6, 30, 1536, 4096, 1 << 20):
                got = ra.pages_per_block(ps, mp, rows)
                assert 1 <= got <= mp
                assert got == ra.pages_per_block(ps, mp, rows)
    # the decode sub-call (max_q 1, G 6): 512 keys; the longctx decode
    # grid then walks 256 / 32 = 8 blocks, not 256 pages
    assert ra.pages_per_block(16, 256, 1 * 6) * 16 == 512
    t, g, d = 1024, 6, 128
    for max_q in (256, 1):
        rows = max_q * g
        keys = ra.pages_per_block(16, 256, rows) * 16
        tile = rows * keys * 4
        assert tile <= ra.SCORE_TILE_BYTES
        assert tile < ra.vmem_limit_bytes(t, max_q, g, d, 2, keys)
    # the prefill sub-call (max_q 256): 256 keys
    assert ra.pages_per_block(16, 256, 256 * g) * 16 == 256


def test_ragged_decode_only_matches_paged_decode_oracle():
    """A decode-only packing must reproduce the single-token paged decode
    oracle slot for slot (same pages, same lengths)."""
    rng = np.random.default_rng(1)
    Hq, Hkv, D, ps, mp, max_q = 4, 2, 8, 4, 4, 4
    segs = [(1, 6), (1, 11), (1, 3)]
    q, kp, vp, pt, qs, ql, kl = _ragged_case(rng, segs, Hq, Hkv, D, ps, mp,
                                             max_q)
    got = kops.ragged_paged_attention(q, kp, vp, pt, qs, ql, kl,
                                      max_q=max_q, impl="pallas",
                                      interpret=True)
    want = ref.paged_decode_reference(q[:, None], kp, vp, pt, kl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=1e-5, rtol=1e-5)


def test_ragged_prefill_chunk_matches_dense_chunk():
    """A prefill-chunk segment (causal within the chunk, full visibility
    of its earlier context) must match dense chunked-prefill attention on
    the linearized cache."""
    rng = np.random.default_rng(2)
    Hq, Hkv, D, ps, mp, max_q = 4, 2, 8, 4, 4, 6
    lo, w = 5, 6  # chunk [5, 11) of an 11-token context
    segs = [(w, lo + w)]
    q, kp, vp, pt, qs, ql, kl = _ragged_case(rng, segs, Hq, Hkv, D, ps, mp,
                                             max_q)
    got = kops.ragged_paged_attention(q, kp, vp, pt, qs, ql, kl,
                                      max_q=max_q, impl="pallas",
                                      interpret=True)
    ka = ref.paged_gather(kp, pt)
    va = ref.paged_gather(vp, pt)
    want = ref.mha_reference(q[None], ka, va, causal=True,
                             kv_len=jnp.asarray([lo + w]),
                             q_offset=jnp.asarray([lo]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("attn_impl,want", [
    ("auto", ("gather", False)),  # the CPU has no TPU: the oracle
    ("gather", ("gather", False)),
    ("pallas", ("pallas", True)),  # explicit: the kernel, interpreted
])
def test_paged_kernel_choice_on_cpu(attn_impl, want):
    from repro.models.common import ModelContext
    from conftest import tiny_dense_spec
    assert jax.default_backend() == "cpu"
    ctx = ModelContext(spec=tiny_dense_spec(), attn_impl=attn_impl)
    assert ctx.paged_kernel() == want


@pytest.mark.parametrize("attn_impl,kernel_calls", [("auto", 0),
                                                     ("pallas", 2)])
def test_unified_step_reaches_kernel_only_when_chosen(monkeypatch, attn_impl,
                                                      kernel_calls):
    """On the CPU "auto" serves the packed step with the gather oracle and
    never traces the ragged kernel; an explicit "pallas" reaches it (once
    per sub-batch of the decode/prefill split), with the same logits."""
    from repro.kernels import ragged_attention
    from repro.models import build_model
    from repro.models.attention import PackedSegs
    from conftest import tiny_dense_spec

    real = ragged_attention.pallas_ragged_paged_attention
    calls = []

    def spy(*a, **k):
        calls.append(k["interpret"])
        return real(*a, **k)
    monkeypatch.setattr(ragged_attention, "pallas_ragged_paged_attention",
                        spy)
    spec = tiny_dense_spec(n_layers=1)

    def logits(impl):
        model = build_model(spec, param_dtype=jnp.float32,
                            compute_dtype=jnp.float32, cache_layout="paged",
                            kv_page_size=4, attn_impl=impl)
        params = model.init(jax.random.key(0))
        cache = model.init_cache(2, 16, layout="paged", n_pages=9)
        # one decode slot (idle) and one 6-token prefill chunk
        packed = PackedSegs(q_start=jnp.asarray([0, 1], jnp.int32),
                            q_len=jnp.asarray([0, 6], jnp.int32),
                            kv_len=jnp.asarray([0, 6], jnp.int32),
                            page_table=jnp.asarray([[0] * 4, [1, 2, 0, 0]],
                                                   jnp.int32),
                            max_q=8, n_decode=1)
        tokens = jnp.arange(9, dtype=jnp.int32) % spec.vocab
        pos = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.arange(8, dtype=jnp.int32)])
        out, _ = model.unified_step(params, cache, tokens, pos, packed)
        return np.asarray(out)[1]

    got = logits(attn_impl)
    assert calls == [True] * kernel_calls
    np.testing.assert_allclose(got, logits("gather"), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# RWKV6 scan kernel + chunked recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,H,N,chunk", [
    (2, 48, 3, 8, 16), (1, 33, 2, 16, 8), (2, 64, 1, 4, 64),
])
def test_rwkv6_pallas_vs_oracle(B, T, H, N, chunk):
    r, k, v = (t((B, T, H, N), 4, scale=0.5), t((B, T, H, N), 5, scale=0.5),
               t((B, T, H, N), 6, scale=0.5))
    w = jax.nn.sigmoid(t((B, T, H, N), 7)) * 0.5 + 0.45
    u = t((H, N), 8, scale=0.3)
    s0 = t((B, H, N, N), 9, scale=0.2)
    want_o, want_s = ref.rwkv6_reference(r, k, v, w, u, s0)
    got_o, got_s = pallas_rwkv6_scan(r, k, v, w, u, s0, chunk=chunk,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=2e-3, rtol=1e-3)


def test_rwkv6_chunked_scan_matches_and_is_differentiable():
    B, T, H, N = 1, 40, 2, 8
    r, k, v = (t((B, T, H, N), 4, scale=0.5), t((B, T, H, N), 5, scale=0.5),
               t((B, T, H, N), 6, scale=0.5))
    w = jax.nn.sigmoid(t((B, T, H, N), 7)) * 0.5 + 0.45
    u = t((H, N), 8, scale=0.3)
    s0 = jnp.zeros((B, H, N, N))
    want, _ = ref.rwkv6_reference(r, k, v, w, u, s0)
    got, _ = kops.rwkv6_scan(r, k, v, w, u, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    def f(r):
        o, _ = kops.rwkv6_scan(r, k, v, w, u, s0, chunk=16)
        return jnp.sum(o * o)

    g = jax.grad(f)(r)
    assert np.all(np.isfinite(np.asarray(g)))


# ---------------------------------------------------------------------------
# Mamba scan
# ---------------------------------------------------------------------------

def test_mamba_chunked_matches_reference():
    B, T, Di, N = 2, 50, 8, 4
    x, dt = t((B, T, Di), 1, scale=0.5), jax.nn.softplus(t((B, T, Di), 2))
    a = -jnp.exp(t((Di, N), 3, scale=0.1))
    b, c = t((B, T, N), 4, scale=0.5), t((B, T, N), 5, scale=0.5)
    d = t((Di,), 6)
    s0 = t((B, Di, N), 7, scale=0.1)
    want_y, want_s = ref.mamba_scan_reference(x, dt, a, b, c, d, s0)
    got_y, got_s = kops.mamba_scan(x, dt, a, b, c, d, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# MoE expert GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F,bc,bf", [
    (4, 40, 24, 56, 16, 16), (2, 16, 32, 32, 16, 32), (8, 8, 8, 8, 8, 8),
])
def test_moe_gemm_vs_oracle(E, C, D, F, bc, bf, dtype):
    x, w = t((E, C, D), 10, dtype), t((E, D, F), 11, dtype)
    want = ref.moe_gemm_reference(x, w)
    got = pallas_expert_gemm(x, w, block_c=bc, block_f=bf, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
