"""The work counts behind the kernel's roofline share and the step's MFU."""

import numpy as np
import pytest

from bench.work import (attn_pairs, matmul_params_per_layer,
                        ragged_call_work, roofline_s, step_flops)

CFG = {"hidden_size": 4096, "num_hidden_layers": 8,
       "num_attention_heads": 48, "num_key_value_heads": 8,
       "head_dim": 128, "intermediate_size": 16384, "hidden_act": "relu2",
       "vocab_size": 256000}


def brute_force(segs, hq, hkv, d, itemsize, page=16):
    """Walk every (query, key) pair of every segment's causal mask, and
    every page up to kv_len with a partial last page cut to kv_len."""
    flops = nbytes = 0
    for q, kv in segs:
        if q == 0:
            continue
        for i in range(q):
            pos = kv - q + i
            flops += 4 * hq * d * sum(1 for k in range(kv) if k <= pos)
        for p in range(0, kv, page):
            nbytes += 2 * min(page, kv - p) * hkv * d * itemsize
        nbytes += 2 * q * hq * d * itemsize
    return flops, nbytes


@pytest.mark.parametrize("seed", range(6))
def test_ragged_work_matches_a_brute_force_count(seed):
    rng = np.random.default_rng(seed)
    segs = [(1, int(rng.integers(1, 300))) for _ in range(5)]  # decode
    for _ in range(3):  # prefill chunks, partial pages and idle rows
        q = int(rng.integers(0, 40))
        segs.append((q, q + int(rng.integers(0, 90))))
    cfg = dict(CFG, num_attention_heads=4, num_key_value_heads=2,
               head_dim=8)
    assert ragged_call_work(segs, cfg, 2) == brute_force(segs, 4, 2, 8, 2)


def test_pairs_of_decode_and_full_prefill():
    assert attn_pairs(1, 100) == 100
    assert attn_pairs(4, 4) == 10
    assert attn_pairs(0, 50) == 0


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert roofline_s(1000, 50, peak) == 10.0
    assert roofline_s(100, 50, peak) == 5.0


def test_step_flops_worked_case():
    # one decode token at context 1000 and a 256-token chunk ending at 512,
    # two sampled segments: per layer 2 x params x 257 tokens plus
    # attention 4 * 48 * 128 * (1000 + 256 * 256 + 256 * 257 / 2); the
    # head twice
    p = 2 * 4096 * 6144 + 2 * 4096 * 1024 + 2 * 4096 * 16384
    assert matmul_params_per_layer(CFG) == p
    attn = 4 * 48 * 128 * (1000 + 256 * 256 + 256 * 257 // 2)
    want = 8 * (2 * p * 257 + attn) + 2 * 2 * 4096 * 256000
    assert step_flops([(1, 1000), (256, 512)], 2, CFG) == want
