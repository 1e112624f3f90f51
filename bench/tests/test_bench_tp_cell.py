"""The tensor-parallel cell's path on a 4-device CPU mesh, against the
plain reference: a tiny MHA SwiGLU stack at ``deepseek_7b.REDUCED``'s
widths (d_model 128, 4 query and 4 KV heads of 32, d_ff 344, untied head)
served at ``serving.tp`` 4, so each shard holds one query and one KV head
(G = 1), a quarter of the FFN and a quarter of the vocabulary.

Each mesh case runs in a child process that sees four CPU devices
(``--xla_force_host_platform_device_count``); the test process itself
keeps its one device.  The ``mesh.collective_pct`` reader is tested here
on synthetic trace summaries."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench.tests.tiny import REPO

TP = 4
CONFIG = {"hidden_size": 128, "num_hidden_layers": 2,
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "head_dim": 32, "intermediate_size": 344, "hidden_act": "silu",
          "vocab_size": 512, "tie_word_embeddings": False,
          "rope_theta": 10000.0, "partial_rotary_factor": 1.0,
          "norm_eps": 1e-6, "serving": {"tp": TP},
          # program readings on the CPU 0-0.004, the fp8 control's
          # 0.08-0.09 (seeds 3, 5)
          "correct": {"max_logit_gap": 0.015}}
# float32 logits of the tp=4 engine against the float32 reference: the
# two differ only by float32 rounding (the order of each matmul's sums,
# the four partial products each psum adds, the online softmax), about
# 6e-7 at logits below 1 in magnitude; 1e-5 leaves 15 times that.  A
# bfloat16 forward rounds every activation to 8 bits and reads about
# 8e-3, 800 times the tolerance.
LOGIT_ATOL = 1e-5
PAGE, CHUNK, MAX_SEQ, SEED = 8, 8, 64, 7


def in_mesh(call: str) -> dict:
    """Run ``call`` (an expression over this module) in a child process
    with four CPU devices; returns the JSON it printed last."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={TP}",
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
    code = ("import json, bench.tests.test_bench_tp_cell as t; "
            f"print(json.dumps(t.{call}))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# what the child processes run
# ---------------------------------------------------------------------------

def cell_run(seed: int, drop_psum: bool = False) -> dict:
    """One run of the tiny tp=4 cell through ``bench.harness.run_cell``;
    ``drop_psum`` leaves out the MLP's all-reduce in every layer."""
    import tempfile

    import repro.launch.runtime as rt
    from bench.tests import tiny
    from repro.models import transformer
    rt.use_compile_cache = lambda root: "off"
    if drop_psum:
        orig = transformer.mlp_block

        def no_psum(spec, ctx, params, x, **kw):
            return orig(spec, ctx.with_(tp_axis=None), params, x, **kw)
        transformer.mlp_block = no_psum
    root = tiny.make_root(tempfile.mkdtemp())
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(CONFIG, f)
    return tiny.run(root, "tiny.closed", seed=seed, seconds=1.5)


def served_logits(dtype_name: str, tokens: list[int], n_prompt: int):
    """Logits of the tp=4 packed forward at ``dtype_name``: the prompt in
    chunked prefill (``CHUNK`` tokens a step, prefill row 0 of the mixed
    profile), then one decode step a token through the paged cache.
    Returns (logits of the last position of each step, those positions).
    The weights are the benchmark's for ``SEED``, bf16 values as served,
    cast to the forward's dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import model as bm
    from repro.models import build_model
    from repro.serving import sharded as shard
    dtype = jnp.dtype(dtype_name)
    model = build_model(bm.model_spec(CONFIG, "tiny-tp"), param_dtype=dtype,
                        compute_dtype=dtype, cache_layout="paged",
                        kv_page_size=PAGE)
    lo, hi = bm.seed_words(SEED)
    mesh = shard.make_engine_mesh(TP, 1)

    def weights():
        layers = [bm.layer_weights(CONFIG, lo, hi, i)
                  for i in range(CONFIG["num_hidden_layers"])]
        tree = bm._to_program(CONFIG, layers,
                              bm.global_weights(CONFIG, lo, hi))
        return jax.tree.map(lambda a: a.astype(dtype), tree)
    params = shard.init_sharded(weights, shard.param_pspecs(model, TP, 1),
                                mesh)
    max_pages = MAX_SEQ // PAGE
    cache = shard.init_sharded(
        lambda: model.init_cache(1, MAX_SEQ, layout="paged",
                                 n_pages=max_pages + 1),
        shard.cache_pspecs(model, TP, 1), mesh)
    ptab = np.arange(1, max_pages + 1, dtype=np.int32)[None]
    # the engine's two profiles: one decode slot and one prefill row of
    # CHUNK tokens, and decode only
    mixed = shard.build_sharded_forward(model, mesh, TP, 1, max_q=CHUNK,
                                        n_decode=1)
    decode = shard.build_sharded_forward(model, mesh, TP, 1, max_q=1,
                                         n_decode=0)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    out, pos = [], []
    for start in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - start)
        tok = np.zeros(1 + CHUNK, np.int32)
        at = np.zeros(1 + CHUNK, np.int32)
        tok[1:1 + n] = tokens[start:start + n]
        at[1:1 + n] = np.arange(start, start + n)
        logits, cache = mixed(params, cache, i32(tok), i32(at), i32([0, 1]),
                              i32([0, n]), i32([0, start + n]),
                              i32(np.repeat(ptab, 2, axis=0)))
        out.append(np.asarray(logits[1], np.float32).tolist())
        pos.append(start + n - 1)
    for k in range(n_prompt, len(tokens)):
        logits, cache = decode(params, cache, i32([tokens[k]]), i32([k]),
                               i32([0]), i32([1]), i32([k + 1]), i32(ptab))
        out.append(np.asarray(logits[0], np.float32).tolist())
        pos.append(k)
    return out, pos


def logit_error(dtype_name: str) -> dict:
    """Largest |served - reference| logit over a 20-token prompt (three
    prefill chunks) and 8 decode steps."""
    import jax.numpy as jnp
    import numpy as np

    from bench import model as bm
    from bench import reference as ref
    tokens = np.random.default_rng(0).integers(
        0, CONFIG["vocab_size"], 28).tolist()
    got, pos = served_logits(dtype_name, tokens, n_prompt=20)
    lo, hi = bm.seed_words(SEED)
    x, key = ref._hidden(CONFIG, np.asarray([tokens]), lo, hi, None)
    h = jnp.pad(x[0], ((0, ref.POS_BLOCK - len(tokens)), (0, 0)))[None]
    want = np.asarray(ref._logits_fn(h, lo, hi, cfg_key=key, quant=None))[0]
    err = np.abs(np.asarray(got) - want[pos])
    return {"max_abs": float(err.max()), "positions": len(pos)}


# ---------------------------------------------------------------------------
# the tests
# ---------------------------------------------------------------------------

def test_tp4_cell_is_correct_through_the_harness():
    out = in_mesh("cell_run(3)")
    gap = out["check"]["max_logit_gap"]
    assert out["correct"] is True and gap["value"] <= gap["limit"]
    assert out["device"]["count"] == TP and out["failed"] == 0
    assert out["check"]["served_tokens_compared"]["value"] > 0


def test_tp4_cell_with_a_dropped_psum_is_not_correct():
    out = in_mesh("cell_run(3, drop_psum=True)")
    gap = out["check"]["max_logit_gap"]
    assert out["correct"] is False and gap["value"] > gap["limit"]


@pytest.mark.parametrize("dtype,within", [("float32", True),
                                          ("bfloat16", False)])
def test_tp4_logits_against_the_float32_reference(dtype, within):
    got = in_mesh(f"logit_error({dtype!r})")
    assert got["positions"] == 3 + 8
    assert (got["max_abs"] <= LOGIT_ATOL) is within, got


# --- mesh.collective_pct on synthetic trace summaries ----------------------

def _summary(ops: dict):
    from bench.trace import Summary
    return Summary(window_s=1.0, busy_s=sum(ops.values()), kernel_s=0.0,
                   ops=ops)


def _collective_pct(trace):
    from types import SimpleNamespace

    from bench.harness import metric_reader
    return metric_reader(REPO, "mesh.collective_pct")(
        SimpleNamespace(trace=trace))


def test_collective_pct_counts_collective_labels_only():
    ops = {"psum": 0.02, "all-gather": 0.01, "all-reduce-start": 0.005,
           "all-reduce-done": 0.005, "fusion": 0.5, "copy": 0.3,
           "ragged_attn (tpu_custom_call)": 0.1, "sort": 0.06}
    assert _collective_pct(_summary(ops)) == pytest.approx(100 * 0.04 / 1.0)
    # a label that only contains a collective's name is no collective
    assert _collective_pct(_summary({"fusion": 1.0, "psum_fusion": 1.0,
                                     "all-gather": 0.5})) == \
        pytest.approx(100 * 0.5 / 2.5)


def test_collective_pct_reads_none_without_a_trace_or_a_collective():
    assert _collective_pct(None) is None
    assert _collective_pct(_summary({"fusion": 1.0, "sort": 0.5})) is None
    assert _collective_pct(_summary({})) is None


@pytest.mark.parametrize("coll", [1e-9, 0.5, 1.0, 1e6])
def test_collective_pct_never_exceeds_100(coll):
    for ops in ({"psum": coll}, {"psum": coll, "fusion": 1.0},
                {"all-gather": coll, "all-reduce": coll}):
        assert 0 < _collective_pct(_summary(ops)) <= 100
