#!/usr/bin/env python
"""Smoke test of the main serving path on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the (pp, tp) mesh path, four chips

One chip: ``qwen1.5-0.5b`` at its published width in bf16, random weights
from ``--seed``, serves 8 requests (prompts of 128-1024 tokens, 32 new
tokens each) through ``ServeEngine`` with the unified, token-packed, paged
step.  The compiled step must contain the Pallas ragged kernel
(``tpu_custom_call``).  Then, in float32 under
``jax.default_matmul_precision("highest")``, two packed steps (prefill
chunks, then decode segments beside a continuing chunk) run through the
Pallas kernels and through the gather oracle (``attn_impl="gather"``);
their logits must agree within ``LOGIT_TOL``.

Four chips: the same float32 logits check for qwen1.5-0.5b at tp=4 and at
tp=2 x pp=2 against tp=1 on the same host, then ``deepseek-7b`` in bf16 at
tp=4 (13.8 GB of weights, created already split over the mesh) serving a
few requests.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any failure
exits non-zero without that line; without a TPU the script fails and never
falls back to the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.launch.runtime import use_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.attention import PackedSegs  # noqa: E402
from repro.serving import EngineConfig, Request, ServeEngine  # noqa: E402
from repro.serving import sharded as shard  # noqa: E402
from repro.serving.engine import EngineMetrics  # noqa: E402
from repro.serving.sampling import SamplingConfig  # noqa: E402

#: float32 logits of two implementations of the same step must agree to
#: max|a - b| <= LOGIT_TOL * max(1, max|b|).  Both sides compute in
#: float32 at the highest matmul precision and differ only in summation
#: order (online softmax over pages vs one masked softmax, or psum
#: partial sums under tp), which stays orders of magnitude below this.
LOGIT_TOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {devs[0].platform}"
                         f" devices); this check never runs on the CPU")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX sees {len(devs)}")
    return devs


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def make_prompts(rng, n: int, lo: int, hi: int, vocab: int) -> list[list]:
    return [rng.integers(0, vocab, size=int(rng.integers(lo, hi + 1)))
            .tolist() for _ in range(n)]


# ---------------------------------------------------------------------------
# float32 logits check: two packed steps through one compiled program
# ---------------------------------------------------------------------------

CHECK_CHUNK = 128
CHECK_MAX_SEQ = 512
CHECK_PAGE = 16


def check_inputs(prompts: list[list]):
    """Host inputs of two steps of the mixed packed profile (n slots, n
    prefill rows, chunk ``CHECK_CHUNK``).  Step 1 prefills each prompt's
    first chunk on its row; step 2 decodes the prompts that completed
    (slot r holds prompt r's pages) beside the next chunk of the longer
    ones.  Step 2's decode tokens come from step 1's logits, so this
    returns step 1 and a builder for step 2."""
    n, c = len(prompts), CHECK_CHUNK
    mp = CHECK_MAX_SEQ // CHECK_PAGE
    pages = np.stack([1 + r * mp + np.arange(mp, dtype=np.int32)
                      for r in range(n)])
    n_segs, t = 2 * n, n + n * c
    q_start = np.concatenate([np.arange(n), n + c * np.arange(n)])

    def step(lo: list[int], dec_tok: dict[int, int]):
        tokens = np.zeros((t,), np.int32)
        pos = np.zeros((t,), np.int32)
        q_len = np.zeros((n_segs,), np.int32)
        kv_len = np.zeros((n_segs,), np.int32)
        ptab = np.zeros((n_segs, mp), np.int32)
        for r, tok in dec_tok.items():  # decode slot r
            tokens[r], pos[r] = tok, len(prompts[r])
            q_len[r], kv_len[r] = 1, len(prompts[r]) + 1
            ptab[r] = pages[r]
        for r, p in enumerate(prompts):  # prefill row r
            w = min(c, len(p) - lo[r])
            if w <= 0:
                continue
            qs = n + r * c
            tokens[qs:qs + w] = p[lo[r]:lo[r] + w]
            pos[qs:qs + w] = np.arange(lo[r], lo[r] + w)
            q_len[n + r], kv_len[n + r] = w, lo[r] + w
            ptab[n + r] = pages[r]
        return (tokens, pos, q_start.astype(np.int32), q_len, kv_len, ptab)

    return n, step


def run_check(fwd, params, cache, prompts):
    """Both steps through ``fwd`` (one AOT-compiled program).  Returns
    (logits of live segments per step, greedy tokens, compiled HLO)."""
    n, step = check_inputs(prompts)
    c = CHECK_CHUNK
    args1 = step([0] * n, {})
    compiled = fwd.lower(params, cache, *args1).compile()
    logits1, cache = compiled(params, cache, *args1)
    logits1 = np.asarray(logits1)
    done = [r for r, p in enumerate(prompts) if len(p) <= c]
    dec = {r: int(np.argmax(logits1[n + r])) for r in done}
    args2 = step([c] * n, dec)
    logits2, _ = compiled(params, cache, *args2)
    logits2 = np.asarray(logits2)
    live1 = args1[3] > 0
    live2 = args2[3] > 0
    out = np.concatenate([logits1[live1], logits2[live2]])
    return out, np.argmax(out, axis=-1), compiled.as_text()


def compare(name: str, got, ref) -> None:
    (lg, tg, _), (lr, tr, _) = got, ref
    delta = float(np.max(np.abs(lg - lr)))
    scale = max(1.0, float(np.max(np.abs(lr))))
    log(f"{name}: max|dlogit| {delta:.3e} (scale {scale:.3e}, limit "
        f"{LOGIT_TOL * scale:.3e}); greedy tokens agree: "
        f"{bool(np.array_equal(tg, tr))} ({int(np.sum(tg == tr))}/"
        f"{tg.size})")
    if not np.all(np.isfinite(lg)):
        raise SystemExit(f"{name}: non-finite logits")
    if delta > LOGIT_TOL * scale:
        raise SystemExit(f"{name}: logits differ by {delta:.3e} > "
                         f"{LOGIT_TOL * scale:.3e}")


def one_device_forward(model):
    def fwd(params, cache, tokens, pos, q_start, q_len, kv_len, ptab):
        packed = PackedSegs(q_start=q_start, q_len=q_len, kv_len=kv_len,
                            page_table=ptab, max_q=CHECK_CHUNK,
                            n_decode=len(q_start) // 2)
        return model.unified_step(params, cache, tokens, pos, packed)
    return jax.jit(fwd, donate_argnums=(1,))


def check_cache(model, n: int, mesh=None, tp=1, pp=1):
    mp = CHECK_MAX_SEQ // CHECK_PAGE

    def init():
        return model.init_cache(n, CHECK_MAX_SEQ, layout="paged",
                                n_pages=n * mp + 1)
    if mesh is None:
        return init()
    return shard.init_sharded(init, shard.cache_pspecs(model, tp, pp), mesh)


def f32_model(spec, **kw):
    return build_model(spec, param_dtype=jnp.float32,
                       compute_dtype=jnp.float32, cache_layout="paged",
                       kv_page_size=CHECK_PAGE, **kw)


def check_prompts(seed: int, vocab: int) -> list[list]:
    rng = np.random.default_rng(seed + 1)
    # one prompt spans two chunks, so step 2 mixes decode and prefill
    return make_prompts(rng, 1, CHECK_CHUNK + 1, 2 * CHECK_CHUNK, vocab) \
        + make_prompts(rng, 3, 16, CHECK_CHUNK, vocab)


def pallas_vs_gather(spec, seed: int) -> None:
    prompts = check_prompts(seed, spec.vocab)
    model = f32_model(spec)
    params = jax.jit(model.init)(jax.random.key(seed))
    with jax.default_matmul_precision("highest"):
        got = run_check(one_device_forward(model), params,
                        check_cache(model, len(prompts)), prompts)
        if "tpu_custom_call" not in got[2]:
            raise SystemExit("float32 check: the Pallas step has no "
                             "tpu_custom_call")
        ref_model = f32_model(spec, attn_impl="gather")
        ref = run_check(one_device_forward(ref_model), params,
                        check_cache(ref_model, len(prompts)), prompts)
    compare("pallas vs gather oracle (float32)", got, ref)


def mesh_vs_one(spec, seed: int) -> None:
    prompts = check_prompts(seed, spec.vocab)
    n = len(prompts)
    model = f32_model(spec)
    params = jax.jit(model.init)(jax.random.key(seed))
    with jax.default_matmul_precision("highest"):
        ref = run_check(one_device_forward(model), params,
                        check_cache(model, n), prompts)
        for tp, pp in ((4, 1), (2, 2)):
            mesh = shard.make_engine_mesh(tp, pp)
            fwd = shard.build_sharded_forward(model, mesh, tp, pp,
                                              max_q=CHECK_CHUNK, n_decode=n)
            p = shard.init_sharded(lambda: model.init(jax.random.key(seed)),
                                   shard.param_pspecs(model, tp, pp), mesh)
            got = run_check(fwd, p, check_cache(model, n, mesh, tp, pp),
                            prompts)
            if "tpu_custom_call" not in got[2]:
                raise SystemExit(f"tp={tp} pp={pp}: no tpu_custom_call")
            compare(f"tp={tp} pp={pp} vs tp=1 (float32)", got, ref)
            del p


# ---------------------------------------------------------------------------
# serving through ServeEngine
# ---------------------------------------------------------------------------

def step_hlo_has_kernel(eng: ServeEngine, dev) -> float:
    """AOT-compile the engine's mixed unified step, assert the Pallas
    ragged kernel is in it, return compile seconds."""
    one = jax.sharding.SingleDeviceSharding(dev)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
    n, t, mp = eng.n_segs, eng.t_pack, eng.max_pages
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
    t0 = time.perf_counter()
    compiled = eng._jit_unified.lower(
        jax.tree.map(sds, eng.params), jax.tree.map(sds, eng.cache),
        i32(t), i32(t), i32(n), i32(n), i32(n), i32(n, mp), key,
        f32(n), i32(n), f32(n)).compile()
    dt = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise SystemExit("the compiled unified step has no tpu_custom_call:"
                         " the Pallas ragged kernel is not on the path")
    return dt


def serve(eng: ServeEngine, prompts: list[list], max_new: int) -> dict:
    eng.metrics = EngineMetrics()
    reqs = [Request(prompt=p, max_new_tokens=max_new,
                    sampling=SamplingConfig(temperature=0.0))
            for p in prompts]
    eng.serve(reqs)
    for r in reqs:
        if r.state != "done" or len(r.output) != max_new:
            raise SystemExit(f"request {r.rid} ({len(r.prompt)}-token "
                             f"prompt) ended {r.state} with "
                             f"{len(r.output)}/{max_new} tokens")
    return eng.metrics.summary(reqs)


def report(tag: str, s: dict, devs) -> None:
    log(f"{tag}: {s['requests_done']} requests, {s['generated_tokens']} "
        f"tokens, {s['steps']} steps, tokens/s {s['tokens_per_s']:.1f}, "
        f"TTFT p50 {s['ttft_s_p50'] * 1e3:.1f} ms, dispatches/step "
        f"{s['dispatches_per_step']:.2f}")
    log(f"{tag}: peak_bytes_in_use " + ", ".join(
        f"{d.id}:{peak_bytes(d)}" for d in devs))


def one_chip(spec, seed: int, devs) -> None:
    model = build_model(spec, param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16)
    params = jax.jit(model.init)(jax.random.key(seed))
    log(f"model: {spec.name}, {model.param_count(params) / 1e6:.1f}M params"
        f", bf16")
    eng = ServeEngine(model, params, EngineConfig(
        cache_layout="paged", unified=True, max_slots=8, max_seq=2048,
        chunk_size=128, prefill_rows=2), rng=jax.random.key(seed))
    log(f"compile unified step: {step_hlo_has_kernel(eng, devs[0]):.1f} s "
        f"(tpu_custom_call present)")
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    serve(eng, make_prompts(rng, 2, 16, 200, spec.vocab), 4)
    log(f"warm-up serve (both step profiles): "
        f"{time.perf_counter() - t0:.1f} s")
    s = serve(eng, make_prompts(rng, 8, 128, 1024, spec.vocab), 32)
    report(f"serve {spec.name} bf16", s, devs[:1])
    del eng, params
    pallas_vs_gather(spec, seed)


def four_chips(spec_small, spec, seed: int, devs) -> None:
    mesh_vs_one(spec_small, seed)
    tp = 4
    model = build_model(spec, param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16)
    mesh = shard.make_engine_mesh(tp, 1)
    t0 = time.perf_counter()
    params = shard.init_sharded(lambda: model.init(jax.random.key(seed)),
                                shard.param_pspecs(model, tp, 1), mesh)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"model: {spec.name}, {n_bytes / 1e9:.2f} GB bf16 params created "
        f"split over tp={tp} in {time.perf_counter() - t0:.1f} s")
    eng = ServeEngine(model, params, EngineConfig(
        cache_layout="paged", unified=True, max_slots=4, max_seq=1024,
        chunk_size=128, prefill_rows=2, tp=tp), rng=jax.random.key(seed))
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    serve(eng, make_prompts(rng, 2, 16, 200, spec.vocab), 4)
    log(f"warm-up serve (both step profiles): "
        f"{time.perf_counter() - t0:.1f} s")
    s = serve(eng, make_prompts(rng, 4, 128, 512, spec.vocab), 16)
    report(f"serve deepseek-7b bf16 tp={tp}", s, devs[:tp])
    worst = max(peak_bytes(d) for d in devs[:tp])
    if worst >= n_bytes:
        raise SystemExit(f"a device peaked at {worst} bytes >= the whole "
                         f"model ({n_bytes}): it was materialised on one "
                         "chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devs = require_tpu(args.chips)
    log(f"cache: {use_compile_cache(ROOT)}")
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    qwen = registry.get_spec("qwen1.5-0.5b")
    if args.chips == 4:
        four_chips(qwen, registry.get_spec("deepseek-7b"), args.seed, devs)
    else:
        one_chip(qwen, args.seed, devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
