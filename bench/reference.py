"""The plain reference, and the comparison that decides ``correct``.

A dense GQA decoder written out in ``jax.numpy`` at float32 and
``Precision.HIGHEST``: RMSNorm, rotary positions (rotate-half, over the
whole head), causal softmax attention in which query head h reads kv head
h // (Hq / Hkv), a squared-ReLU or SwiGLU MLP, a final norm and the head.
It imports nothing of the program and takes nothing the program made: it
draws the same weights from the seed itself (``bench.model``), layer by
layer, after the window.

The comparison: each sampled request is run once over its prompt and the
tokens it was served, and at every served position the gap by which the
served token's logit lies below the reference's best is read.  The
widest gap over the sample is the number compared.  Greedy tokens only.

The control (``quant="fp8"``) is the same reference computed in float8
e4m3, the step below the served bf16: every weight matrix, and both
operands of every contraction, rounded through e4m3 with one scale per
tensor.  At the same positions it reads the gap, in the float32 logits,
of the token the fp8 model puts first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import model as bm

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per attention block
POS_BLOCK = 128  # served positions per head block


def _fp8(x):
    """Round through float8 e4m3 with one scale per tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0  # e4m3 max
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(eq, a, b, quant=None):
    """A float32 contraction; the fp8 control rounds both operands."""
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _f32(w, quant):
    w = w.astype(jnp.float32)
    return _fp8(w) if quant == "fp8" and w.ndim > 1 else w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, H, D), rotate-half over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, quant):
    """Causal GQA over one sequence.  q: (S, Hq, D); k, v: (S, Hkv, D)."""
    s, hq, d = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    bq = min(Q_BLOCK, s)
    kpos = jnp.arange(s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, bq, axis=0)
        sc = _dot("qhd,khd->hqk", qb, k, quant) / np.sqrt(d)
        qpos = start + jnp.arange(bq)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return _dot("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, quant)

    out = jax.lax.map(block, jnp.arange(0, s, bq))
    return out.reshape(s, hq, d)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _layer(x, lo, hi, layer, *, cfg_key, quant):
    cfg = dict(cfg_key)
    w = {n: _f32(a, quant)
         for n, a in bm.layer_weights(cfg, lo, hi, layer).items()}
    eps = float(cfg["norm_eps"])
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    n, s, _ = x.shape
    pos = jnp.arange(s)

    def attend(xi):
        h = _rms(xi, w["attn_norm"], eps)
        q = _rope(_dot("sd,dk->sk", h, w["wq"], quant).reshape(s, hq, d),
                  pos, cfg["rope_theta"])
        k = _rope(_dot("sd,dk->sk", h, w["wk"], quant).reshape(s, hkv, d),
                  pos, cfg["rope_theta"])
        v = _dot("sd,dk->sk", h, w["wv"], quant).reshape(s, hkv, d)
        o = _attention(q, k, v, quant).reshape(s, hq * d)
        return xi + _dot("sk,kd->sd", o, w["wo"], quant)

    x = jax.lax.map(attend, x)
    h = _rms(x, w["mlp_norm"], eps)
    up = _dot("nsd,df->nsf", h, w["w_up"], quant)
    if cfg["hidden_act"] == "silu":
        up = jax.nn.silu(_dot("nsd,df->nsf", h, w["w_gate"], quant)) * up
    else:
        up = jnp.square(jax.nn.relu(up))
    return x + _dot("nsf,fd->nsd", up, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _embed(tokens, lo, hi, *, cfg_key, quant):
    cfg = dict(cfg_key)
    shape = bm.leaf_shapes(cfg)["embed"]
    table = bm._leaf(lo, hi, bm._GLOBAL, "embed", shape, jnp.bfloat16)
    if quant == "fp8":
        return _f32(table, quant)[tokens]
    return table[tokens].astype(jnp.float32)


def _head_weights(cfg, lo, hi, quant):
    name = "embed" if cfg.get("tie_word_embeddings") else "lm_head"
    w = bm._leaf(lo, hi, bm._GLOBAL, name, bm.leaf_shapes(cfg)[name],
                 jnp.bfloat16)
    w = _f32(w, quant)
    return w.T if name == "embed" else w


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _logits_fn(h, lo, hi, *, cfg_key, quant):
    """h: (B, POS_BLOCK, d) final hidden -> (B, POS_BLOCK, V) logits."""
    cfg = dict(cfg_key)
    w = _head_weights(cfg, lo, hi, quant)
    norm = jnp.ones((cfg["hidden_size"],), jnp.float32)

    def one(hb):
        return _dot("pd,dv->pv", _rms(hb, norm, float(cfg["norm_eps"])), w,
                    quant)
    return jax.lax.map(one, h)


@jax.jit
def _gap_of(ref_logits, tokens):
    """Gap of ``tokens`` below the best reference logit, per position."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return best - got


def _hidden(cfg, tokens, lo, hi, quant):
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if not isinstance(v, (dict, list))))
    x = _embed(jnp.asarray(tokens), lo, hi, cfg_key=key, quant=quant)
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer(x, lo, hi, np.uint32(layer), cfg_key=key, quant=quant)
    return x, key


def served_gaps(cfg: dict, seed: int, samples, seq_len: int, n_slots: int,
                out_len: int, control: bool = False) -> dict:
    """Reference over each sampled ``(prompt, served tokens)``.

    ``seq_len``/``n_slots``/``out_len`` fix the shapes (sequence bucket,
    requests, served positions per request) so one compiled program serves
    every run.  Returns the widest gap of a served token, the served
    tokens compared and, with ``control``, the widest gap of the fp8
    model's first choice."""
    if not samples:
        raise ValueError("no served request to compare")
    if len(samples) > n_slots:
        raise ValueError(f"{len(samples)} samples > {n_slots} slots")
    lo, hi = bm.seed_words(seed)
    tokens = np.zeros((n_slots, seq_len), np.int32)
    idx = np.zeros((n_slots, out_len), np.int32)
    served = np.zeros((n_slots, out_len), np.int32)
    valid = np.zeros((n_slots, out_len), bool)
    for i, (prompt, out) in enumerate(samples):
        out = list(out)[:out_len]
        seq = list(prompt) + out[:-1]
        if len(seq) > seq_len:
            raise ValueError(f"sample {i}: {len(seq)} tokens > {seq_len}")
        tokens[i, :len(seq)] = seq
        m = len(out)
        idx[i, :m] = len(prompt) - 1 + np.arange(m)
        served[i, :m] = out
        valid[i, :m] = True

    def head_rows(x):
        """Final hidden rows at the served positions, (B, POS_BLOCK, d)."""
        rows = jnp.take_along_axis(x, jnp.asarray(idx)[..., None], axis=1)
        rows = rows.reshape(-1, x.shape[-1])
        pad = (-rows.shape[0]) % POS_BLOCK
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        return rows.reshape(-1, POS_BLOCK, x.shape[-1])

    x, key = _hidden(cfg, tokens, lo, hi, None)
    h_ref = head_rows(x)
    del x
    n = n_slots * out_len
    flat_served = np.pad(served.reshape(-1), (0, (-n) % POS_BLOCK))
    ref_logits = _logits_fn(h_ref, lo, hi, cfg_key=key, quant=None)
    gaps = np.asarray(_gap_of(ref_logits, jnp.asarray(
        flat_served.reshape(h_ref.shape[:2]))).reshape(-1)[:n])
    mask = valid.reshape(-1)
    out = {"gap": float(np.max(gaps[mask])), "tokens": int(mask.sum())}
    if control:
        del ref_logits
        x_low, _ = _hidden(cfg, tokens, lo, hi, "fp8")
        h_low = head_rows(x_low)
        del x_low
        low_logits = _logits_fn(h_low, lo, hi, cfg_key=key, quant="fp8")
        first = jnp.argmax(low_logits, axis=-1)
        del low_logits
        ref_logits = _logits_fn(h_ref, lo, hi, cfg_key=key, quant=None)
        cgaps = np.asarray(_gap_of(ref_logits, first).reshape(-1)[:n])
        out["control_gap"] = float(np.max(cgaps[mask]))
    return out
