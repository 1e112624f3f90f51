"""Work the algorithm needs: operations and bytes of the ragged attention
kernel, and of a whole step, from each step's live segments.

A segment is ``(q_len, kv_len)``: q_len new query tokens of one request
whose context after this step holds kv_len tokens.  Only what the
algorithm needs is counted, never what the kernel's grid happens to walk:
the causal (query, key) pairs of the segment's live rows, and each of its
kv_len keys and values read once per kv head plus its queries and outputs
once.  A kernel that stops walking dead pages, or stops computing padded
query rows, then reads a higher share, and no correct kernel reads over
100%.
"""

from __future__ import annotations


def attn_pairs(q_len: int, kv_len: int) -> int:
    """Causal (query, key) pairs of one segment: query i (of q_len) sits at
    position kv_len - q_len + i and sees keys 0..that position."""
    return q_len * (kv_len - q_len) + q_len * (q_len + 1) // 2


def ragged_call_work(segs, cfg: dict, itemsize: int) -> tuple[int, int]:
    """(flops, bytes) one layer's kernel call needs over ``segs``."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    flops = nbytes = 0
    for q, kv in segs:
        if q <= 0:
            continue
        flops += 4 * hq * d * attn_pairs(q, kv)  # QK^T and PV, 2 each
        nbytes += (2 * kv * hkv * d + 2 * q * hq * d) * itemsize
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])


def matmul_params_per_layer(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    n_ffn = 3 if cfg["hidden_act"] == "silu" else 2
    return 2 * d * q + 2 * d * kv + n_ffn * d * f


def step_flops(segs, n_sampled: int, cfg: dict) -> int:
    """Model FLOPs one step needs: 2 x matmul params per processed token,
    attention over each token's causal context, and the head once per
    sampled segment."""
    layers = cfg["num_hidden_layers"]
    tokens = sum(q for q, _ in segs if q > 0)
    attn, _ = ragged_call_work(segs, cfg, 0)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"] * n_sampled
    return (layers * (2 * matmul_params_per_layer(cfg) * tokens + attn)
            + head)
