"""A configuration file -> the program's model, engine settings and weights.

Weights are random and come from ``--seed`` alone.  Every leaf is drawn
from its own key (seed, layer, leaf name) as 16 random bits mapped to a
uniform grid whose step is a power of two, so its bf16 value is exact
whichever program computes it: the serving weights (one jitted call, on
the device, already in bf16 and, on a mesh, already split) and the plain
reference's (layer by layer, after the window) are the same numbers, and
the reference takes nothing the program made.  The spread of each leaf is
near the program's own init: 1/sqrt(fan_in) for projections, 0.02 for the
embedding and head, 1 for norm scales.
"""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

#: layer leaves in canonical order (their index is part of each leaf's key)
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_up",
                "w_gate", "w_down")
GLOBAL_LEAVES = ("embed", "final_norm", "lm_head")
_GLOBAL = 1 << 20  # the "layer" index the global leaves are keyed under

#: keys of a configuration file the program can serve as they stand
_ACTS = {"relu2": "relu2", "silu": "swiglu"}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def check_servable(cfg: dict) -> None:
    """Refuse what the program cannot serve as the file states it."""
    if cfg.get("hidden_act") not in _ACTS:
        raise ValueError(f"hidden_act {cfg.get('hidden_act')!r} not in "
                         f"{sorted(_ACTS)}")
    if float(cfg.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError("the program rotates whole heads: "
                         "partial_rotary_factor must be 1.0")
    if float(cfg["norm_eps"]) != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6")
    if cfg.get("attention_bias") or cfg.get("mlp_bias"):
        raise ValueError("biases are not served")


def model_spec(cfg: dict, name: str):
    from repro.core.modelspec import AttnSpec, ModelSpec
    check_servable(cfg)
    return ModelSpec(
        name=name, d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        attn=AttnSpec(kind="full", causal=True),
        act=_ACTS[cfg["hidden_act"]], norm="rmsnorm", pos="rope",
        rope_theta=float(cfg["rope_theta"]),
        tied_embeddings=bool(cfg.get("tie_word_embeddings", False)))


def leaf_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    shapes = {"attn_norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
              "wo": (q, d), "mlp_norm": (d,), "w_up": (d, f),
              "w_down": (f, d), "embed": (v, d), "final_norm": (d,),
              "lm_head": (d, v)}
    if cfg["hidden_act"] == "silu":
        shapes["w_gate"] = (d, f)
    if cfg.get("tie_word_embeddings"):
        del shapes["lm_head"]
    return shapes


def _grid_step(name: str, shape: tuple[int, ...]) -> float | None:
    """Power-of-two step of the leaf's uniform grid (None: all ones)."""
    if name.endswith("norm"):
        return None
    std = 0.02 if name in ("embed", "lm_head") else 1.0 / math.sqrt(shape[0])
    half_width = std * math.sqrt(3.0)  # uniform(-a, a) has std a/sqrt(3)
    return 2.0 ** round(math.log2(half_width / 32768.0))


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """A seed of any size up to 2**62 as two 31-bit words."""
    if seed < 0:
        raise ValueError("--seed must be >= 0")
    return np.uint32(seed & 0x7FFFFFFF), np.uint32((seed >> 31) & 0x7FFFFFFF)


def _leaf(lo, hi, layer, name: str, shape, dtype):
    step = _grid_step(name, shape)
    if step is None:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(jax.random.key(lo), hi)
    key = jax.random.fold_in(jax.random.fold_in(key, layer),
                             (LAYER_LEAVES + GLOBAL_LEAVES).index(name))
    bits = jax.random.bits(key, shape, jnp.uint16)
    # integer - 32768 is exact in float32, times a power of two too: the
    # value is the same in every program that computes it
    return ((bits.astype(jnp.float32) - 32768.0) * step).astype(dtype)


def layer_weights(cfg: dict, lo, hi, layer, dtype=jnp.bfloat16) -> dict:
    shapes = leaf_shapes(cfg)
    return {n: _leaf(lo, hi, layer, n, shapes[n], dtype)
            for n in LAYER_LEAVES if n in shapes}


def global_weights(cfg: dict, lo, hi, dtype=jnp.bfloat16) -> dict:
    shapes = leaf_shapes(cfg)
    return {n: _leaf(lo, hi, _GLOBAL, n, shapes[n], dtype)
            for n in GLOBAL_LEAVES if n in shapes}


def _to_program(cfg: dict, layers: list[dict], glob: dict) -> dict:
    """Canonical leaves -> the program's parameter tree (one stacked
    position: every layer of a dense stack is of one class)."""
    stack = {n: jnp.stack([w[n] for w in layers]) for n in layers[0]}
    ffn = {"norm": stack["mlp_norm"], "w_up": stack["w_up"],
           "w_down": stack["w_down"]}
    if "w_gate" in stack:
        ffn["w_gate"] = stack["w_gate"]
    tree = {"embed": glob["embed"],
            "layers": {"pos0": {
                "mixer": {"norm": stack["attn_norm"], "wq": stack["wq"],
                          "wk": stack["wk"], "wv": stack["wv"],
                          "wo": stack["wo"]},
                "ffn": ffn}},
            "final_norm": glob["final_norm"]}
    if "lm_head" in glob:
        tree["lm_head"] = glob["lm_head"]
    return tree


def program_params(cfg: dict, model, seed: int, mesh=None, pspecs=None):
    """The served weights, made on the device in one jitted call (split
    over ``mesh`` by ``pspecs`` where given).  Checks the tree against
    the program's own init, shape by shape."""
    n_layers = cfg["num_hidden_layers"]

    def make(lo, hi):
        layers = [layer_weights(cfg, lo, hi, i) for i in range(n_layers)]
        return _to_program(cfg, layers, global_weights(cfg, lo, hi))

    want = jax.eval_shape(model.init, jax.random.key(0))
    lo, hi = seed_words(seed)
    got = jax.eval_shape(make, lo, hi)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter tree differs from the "
                         "benchmark's weight layout: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
    out = None
    if mesh is not None:
        out = jax.tree.map(lambda p: jax.sharding.NamedSharding(mesh, p),
                           pspecs)
    return jax.jit(make, out_shardings=out)(lo, hi)


def bench_root() -> str:
    return os.path.dirname(os.path.abspath(__file__))
