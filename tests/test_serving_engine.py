"""Rebuilt ServeEngine: concurrent batched prefills, device-side sampling,
eos / max_seq early exit with slot reuse, and metrics sanity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build_model
from repro.serving import EngineConfig, Request, ServeEngine
from repro.serving.sampling import SamplingConfig, sample, sample_slots

from conftest import tiny_dense_spec


@pytest.fixture(scope="module")
def served():
    spec = tiny_dense_spec()
    model = build_model(spec, mesh=None, param_dtype=jnp.float32,
                        compute_dtype=jnp.float32)
    params = model.init(jax.random.key(7))
    return spec, model, params


def _greedy_reference(model, params, prompt, n, max_seq=128):
    """Token-by-token greedy decode as ground truth (the seed engine's
    single-request output — its tests assert this same equivalence)."""
    cache = model.init_cache(1, max_seq)
    logits, cache = model.prefill(
        params, jnp.asarray([prompt], jnp.int32), cache=cache)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n - 1):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_concurrent_prefills_mixed_lengths_match_reference(served):
    """Mixed prompt lengths force concurrent prefill rows through both the
    full-width batched path and per-width partial-chunk groups; every
    request must still decode exactly the reference tokens."""
    spec, model, params = served
    rng = np.random.default_rng(3)
    lengths = [3, 11, 4, 17, 9, 5, 23, 8]
    prompts = [[int(t) for t in rng.integers(0, spec.vocab, size=n)]
               for n in lengths]
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=4, max_seq=64, chunk_size=4,
                                   prefill_rows=3))
    reqs = eng.serve([Request(prompt=p, max_new_tokens=6) for p in prompts])
    assert all(r.state == "done" for r in reqs)
    for p, r in zip(prompts, reqs):
        assert r.output == _greedy_reference(model, params, p, 6), \
            "batched prefill changed outputs"


def test_greedy_equivalence_fixed_prompt_set(served):
    """Acceptance fixture: fixed prompt set, greedy outputs must be
    token-identical to sequential reference decoding (= seed engine)."""
    spec, model, params = served
    prompts = [[5, 9, 2, 17, 33, 4, 8, 1], [7, 7, 7], [100, 3, 50, 2, 1],
               [42] * 10]
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=2, max_seq=64, chunk_size=4,
                                   prefill_rows=2))
    reqs = eng.serve([Request(prompt=p, max_new_tokens=8) for p in prompts])
    for p, r in zip(prompts, reqs):
        assert r.output == _greedy_reference(model, params, p, 8)


def test_eos_early_exit_and_slot_reuse(served):
    spec, model, params = served
    prompt = [5, 9, 2, 17, 33, 4]
    want = _greedy_reference(model, params, prompt, 12)
    eos = want[4]
    stop = want.index(eos)  # first occurrence ends the request
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=2, max_seq=64, chunk_size=4))
    # 5 identical requests over 2 slots: early exit must recycle slots
    reqs = eng.serve([Request(prompt=list(prompt), max_new_tokens=12,
                              eos_id=eos) for _ in range(5)])
    for r in reqs:
        assert r.state == "done"
        assert r.output == want[:stop + 1]
    assert sorted(eng.free_slots) == [0, 1]  # all slots back in the pool
    assert not eng.active and not eng.queue


def test_max_seq_early_exit(served):
    spec, model, params = served
    prompt = list(range(1, 11))  # 10 tokens
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=1, max_seq=16, chunk_size=4,
                                   prefill_rows=1))
    [req] = eng.serve([Request(prompt=prompt, max_new_tokens=64)])
    assert req.state == "done"
    # lengths hit max_seq-1: prefill(10) + first token + 5 decode steps
    assert len(req.output) == 16 - 10


def test_single_transfer_per_decode_step(served):
    """The rebuilt decode path makes exactly one device->host transfer per
    step regardless of how many slots are active."""
    spec, model, params = served
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=4, max_seq=64, chunk_size=8))
    reqs = [Request(prompt=[1 + i, 2, 3], max_new_tokens=4)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    calls = {"n": 0}
    orig = jax.device_get

    def counting_device_get(x, *a, **kw):
        calls["n"] += 1
        return orig(x, *a, **kw)

    eng2 = ServeEngine(model, params,
                       EngineConfig(max_slots=4, max_seq=64, chunk_size=8))
    for i in range(4):
        eng2.submit(Request(prompt=[1 + i, 2, 3], max_new_tokens=4))
    eng2.run(max_steps=1)  # all 4 prompts admitted is fine; warm caches
    jax.device_get = counting_device_get
    try:
        before = calls["n"]
        eng2._decode_step()
        assert calls["n"] - before == 1
    finally:
        jax.device_get = orig


def test_ttft_monotone_in_queue_position(served):
    """Under decode_priority, earlier-queued equal-length requests get
    first tokens no later than later-queued ones (steps and wall-clock)."""
    spec, model, params = served
    prompts = [[3 + i, 1, 4, 1, 5, 9] for i in range(6)]
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=2, max_seq=64, chunk_size=4,
                                   prefill_rows=1, record_step_log=True))
    reqs = eng.serve([Request(prompt=p, max_new_tokens=4) for p in prompts])
    ttfts = [r.ttft_steps for r in sorted(reqs, key=lambda r: r.rid)]
    assert ttfts == sorted(ttfts), ttfts
    walls = [r.ttft_s for r in sorted(reqs, key=lambda r: r.rid)]
    assert all(w >= 0 for w in walls)
    assert walls == sorted(walls), walls


def test_metrics_sanity(served):
    spec, model, params = served
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=3, max_seq=64, chunk_size=4,
                                   record_step_log=True))
    reqs = eng.serve([Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=5)
                      for _ in range(5)])
    m = eng.metrics.summary(reqs)
    assert m["generated_tokens"] == sum(len(r.output) for r in reqs) == 25
    assert m["tokens_per_s"] > 0 and m["wall_s"] > 0
    assert 0 < m["mean_slot_occupancy"] <= 1
    assert m["requests_done"] == 5
    assert m["ttft_s_mean"] > 0 and m["ttft_s_p95"] >= m["ttft_s_p50"]
    assert m["tpot_s_mean"] > 0
    assert m["prefill_calls"] >= 1 and m["prefill_tokens"] == 25
    log = eng.metrics.step_log  # one StepRecord per step, in order
    assert [r.step for r in log] == list(range(1, eng.steps + 1))
    assert sorted(rid for r in log for rid in r.admitted) == \
        sorted(r.rid for r in reqs)


def test_sample_slots_matches_sample_rowwise():
    """Per-slot device sampling must agree with the config-based oracle."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    keys = jax.random.split(jax.random.key(5), 4)
    cfgs = [SamplingConfig(),  # greedy
            SamplingConfig(temperature=0.7),
            SamplingConfig(temperature=1.0, top_k=5),
            SamplingConfig(temperature=0.5, top_p=0.8)]
    temps = jnp.asarray([c.temperature for c in cfgs])
    topks = jnp.asarray([c.top_k for c in cfgs], jnp.int32)
    topps = jnp.asarray([c.top_p for c in cfgs])
    got = sample_slots(logits, keys, temps, topks, topps)
    for i, c in enumerate(cfgs):
        want = sample(logits[i:i + 1], keys[i], c)
        assert int(got[i]) == int(want[0]), (i, c)


def _sample_slots_oracle(logits, keys, temperature, top_k, top_p):
    """``sample_slots`` as it was before its filters moved behind a
    ``lax.cond``: every row divided, sorted twice and drawn, every step."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    lf = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-8)[:, None]
    desc = jnp.sort(lf, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_k - 1, 0, v - 1)
    kth = jnp.take_along_axis(desc, k_idx[:, None], axis=-1)
    lf = jnp.where((top_k[:, None] > 0) & (lf < kth), -jnp.inf, lf)
    desc = jnp.sort(lf, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(desc, cutoff_idx[:, None], axis=-1)
    lf = jnp.where((top_p[:, None] < 1.0) & (lf < cutoff), -jnp.inf, lf)

    stochastic = jax.vmap(jax.random.categorical)(keys, lf).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, stochastic)


# (temperature, top_k, top_p) per row
_SAMPLING_CASES = {
    "all_greedy": [(0.0, 0, 1.0), (0.0, 5, 1.0), (0.0, 0, 0.5),
                   (0.0, 3, 0.9), (-1.0, 0, 1.0), (0.0, 40, 0.2)],
    "one_stochastic": [(0.0, 0, 1.0), (0.0, 5, 0.9), (0.9, 0, 1.0),
                       (0.0, 0, 1.0), (0.0, 2, 1.0), (0.0, 0, 0.7)],
    "all_stochastic": [(0.7, 0, 1.0), (1.0, 5, 1.0), (0.5, 0, 0.8),
                       (1.3, 20, 0.9), (0.2, 1, 1.0), (2.0, 0, 0.99)],
    "top_k_only": [(1.0, 1, 1.0), (0.8, 5, 1.0), (1.0, 50, 1.0),
                   (1.5, 3, 1.0), (0.0, 7, 1.0), (1.0, 100000, 1.0)],
    "top_p_only": [(1.0, 0, 0.5), (0.8, 0, 0.9), (1.2, 0, 0.99),
                   (1.0, 0, 0.05), (0.0, 0, 0.7), (2.0, 0, 0.3)],
    "top_k_top_p": [(1.0, 5, 0.5), (0.8, 50, 0.9), (1.2, 2, 0.99),
                    (1.0, 20, 0.05), (0.0, 10, 0.7), (2.0, 200, 0.3)],
}


@pytest.mark.parametrize("vocab", [1000, 257])
@pytest.mark.parametrize("case", sorted(_SAMPLING_CASES))
def test_sample_slots_matches_unconditional_filters(case, vocab):
    """Skipping the filters for an all-greedy batch changes no token: the
    same keys give the same tokens as the unconditional body, for greedy,
    mixed and stochastic batches and each filter alone or together."""
    rows = _SAMPLING_CASES[case]
    rng = np.random.default_rng(vocab)
    logits = jnp.asarray(3.0 * rng.normal(size=(len(rows), vocab)),
                         jnp.float32)
    keys = jax.random.split(jax.random.key(17), len(rows))
    temps = jnp.asarray([r[0] for r in rows], jnp.float32)
    topks = jnp.asarray([r[1] for r in rows], jnp.int32)
    topps = jnp.asarray([r[2] for r in rows], jnp.float32)
    args = (logits, keys, temps, topks, topps)
    got = np.asarray(jax.jit(sample_slots)(*args))
    want = np.asarray(jax.jit(_sample_slots_oracle)(*args))
    np.testing.assert_array_equal(got, want)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    is_greedy = np.asarray(temps) <= 0
    np.testing.assert_array_equal(got[is_greedy], greedy[is_greedy])


def _primitives(jaxpr, into_cond: bool) -> list[str]:
    """Primitive names of ``jaxpr`` and of the jaxprs nested in it;
    ``cond`` branches only when ``into_cond``."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names += _primitives(inner, into_cond)
    return names


def test_sample_slots_sorts_only_inside_cond():
    """The vocabulary sorts and the temperature divide live in the
    ``cond`` branch alone: outside it only the argmax and the any-row
    test run, so a greedy batch pays for neither."""
    b, v = 3, 64
    jaxpr = jax.make_jaxpr(sample_slots)(
        jnp.zeros((b, v), jnp.bfloat16),
        jax.random.split(jax.random.key(0), b),
        jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), jnp.float32)).jaxpr
    outside = _primitives(jaxpr, into_cond=False)
    assert outside.count("cond") == 1, outside
    assert "sort" not in outside and "div" not in outside, outside
    [cond] = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    branches = [_primitives(br.jaxpr, into_cond=True)
                for br in cond.params["branches"]]
    assert sorted(br.count("sort") for br in branches) == [0, 2], branches
    assert any("div" in br for br in branches), branches


@pytest.mark.parametrize("mode", ["unified", "spec"])
def test_step_record_counts_stochastic_segments(served, mode):
    """``StepRecord.stochastic`` reads 0 on every step of greedy traffic,
    and otherwise the number of temperature > 0 requests the step
    sampled a token for; a finished stochastic request leaves its idle
    slot greedy."""
    spec, model, params = served
    kw = dict(record_step_log=True)
    draft = {}
    if mode == "spec":
        kw["n_spec"] = 2
        draft = dict(draft_model=model, draft_params=params)
    eng = ServeEngine(model, params, _unified_cfg(True, **kw),
                      rng=jax.random.key(3), **draft)

    def drive(reqs):
        for r in reqs:
            eng.submit(r)
        want = []
        while eng.busy:
            before = [len(r.output) for r in reqs]
            eng.step()
            grew = [len(r.output) > n for r, n in zip(reqs, before)]
            want.append(sum(g for g, r in zip(grew, reqs)
                            if r.sampling.temperature > 0))
        return want

    greedy = [Request(prompt=[3 + i] * (2 + 3 * i), max_new_tokens=5)
              for i in range(3)]
    n0 = len(eng.metrics.step_log)
    assert drive(greedy) == [0] * (len(eng.metrics.step_log) - n0)
    assert all(r.stochastic == 0 for r in eng.metrics.step_log)

    mixed = [Request(prompt=[9, 8, 7, 6, 5], max_new_tokens=8,
                     sampling=SamplingConfig(temperature=0.8, top_k=20)),
             Request(prompt=[1, 2, 3], max_new_tokens=20),
             Request(prompt=[4] * 9, max_new_tokens=4,
                     sampling=SamplingConfig(temperature=1.0, top_p=0.9)),
             Request(prompt=[6, 6], max_new_tokens=20)]
    n0 = len(eng.metrics.step_log)
    want = drive(mixed)
    got = [r.stochastic for r in eng.metrics.step_log[n0:]]
    assert got == want
    assert max(got) == 2 and got[-1] == 0, got


def test_decode_feed_stays_on_device(served):
    """Steady-state decode never re-uploads the host token mirror: the
    sampled tokens feed the next step from the donated device buffer.
    Corrupting the host mirror mid-decode must not change outputs."""
    spec, model, params = served
    prompt = [5, 9, 2, 17, 33, 4]
    want = _greedy_reference(model, params, prompt, 10)
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=2, max_seq=64, chunk_size=8,
                                   prefill_rows=1))
    [req] = [Request(prompt=list(prompt), max_new_tokens=10)]
    eng.submit(req)
    while not eng.active:
        eng.step()
    eng.step()  # one decode step: the device feed buffer is now primed
    assert eng._dev_tokens is not None
    eng._tokens[:] = 0  # corrupt the host mirror: it must not be read
    eng.run()
    assert req.state == "done" and req.output == want


# ---------------------------------------------------------------------------
# unified token-packed step
# ---------------------------------------------------------------------------

def _unified_cfg(unified, **kw):
    base = dict(max_slots=4, max_seq=64, chunk_size=4, prefill_rows=2,
                cache_layout="paged", page_size=8, unified=unified)
    base.update(kw)
    return EngineConfig(**base)


def test_unified_matches_two_dispatch_mixed_workload(served):
    """Acceptance: greedy outputs token-identical between the unified
    (one-dispatch) step and the retained two-dispatch path on a mixed
    prompt-length workload with concurrent prefills, and both match the
    sequential reference."""
    spec, model, params = served
    rng = np.random.default_rng(11)
    lengths = [3, 11, 4, 17, 9, 5, 23, 8, 2, 13]
    prompts = [[int(t) for t in rng.integers(0, spec.vocab, size=n)]
               for n in lengths]

    outs = {}
    for unified in (False, True):
        eng = ServeEngine(model, params, _unified_cfg(unified))
        reqs = eng.serve([Request(prompt=list(p), max_new_tokens=6)
                          for p in prompts])
        assert all(r.state == "done" for r in reqs)
        outs[unified] = [r.output for r in reqs]
    assert outs[True] == outs[False], "unified step changed outputs"
    for p, out in zip(prompts, outs[True]):
        assert out == _greedy_reference(model, params, p, 6)


def test_unified_matches_two_dispatch_under_preemption(served):
    """A pool small enough to force victim preemption mid-decode must
    still produce token-identical outputs in both implementations."""
    spec, model, params = served
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(0, spec.vocab, size=n)]
               for n in [13, 11, 14, 12, 9, 15]]
    outs, engines = {}, {}
    for unified in (False, True):
        eng = ServeEngine(model, params,
                          _unified_cfg(unified, max_seq=32, page_size=4,
                                       n_pages=11))
        reqs = eng.serve([Request(prompt=list(p), max_new_tokens=10)
                          for p in prompts])
        assert all(r.state == "done" for r in reqs)
        outs[unified] = [r.output for r in reqs]
        engines[unified] = eng
    assert outs[True] == outs[False]
    assert engines[True].metrics.preemptions \
        == engines[False].metrics.preemptions > 0


def test_unified_matches_two_dispatch_quantized_kv(served):
    """The int8 KV path quantizes per token either way (scratch-then-
    scatter vs direct-to-page), so outputs must stay identical too."""
    spec, _, _ = served
    model = build_model(spec, mesh=None, param_dtype=jnp.float32,
                        compute_dtype=jnp.float32, kv_quant=True)
    params = model.init(jax.random.key(7))
    prompts = [[5, 9, 2, 17, 33], [7, 7, 7], [42] * 9, [3, 1, 4, 1, 5, 9]]
    outs = {}
    for unified in (False, True):
        eng = ServeEngine(model, params, _unified_cfg(unified))
        reqs = eng.serve([Request(prompt=list(p), max_new_tokens=5)
                          for p in prompts])
        assert all(r.state == "done" for r in reqs)
        outs[unified] = [r.output for r in reqs]
    assert outs[True] == outs[False]


def test_unified_one_dispatch_one_transfer_per_step(served):
    """Acceptance: with >= 2 concurrent prefills in flight, every unified
    step issues exactly one jitted dispatch and one device->host
    transfer (the two-dispatch path needs strictly more)."""
    spec, model, params = served
    eng = ServeEngine(model, params, _unified_cfg(True))
    # two long prompts + short ones: prefills overlap across steps
    prompts = [[1 + i] * 14 for i in range(2)] + [[7, 8, 9], [4, 5]]
    for p in prompts:
        eng.submit(Request(prompt=list(p), max_new_tokens=5))
    eng.step()  # admit both long prompts; first packed step
    assert len(eng._prefills) >= 2, "need >= 2 concurrent prefills"
    base_d, base_t = eng.metrics.dispatches, eng.metrics.transfers_d2h
    assert base_d == eng.metrics.steps == base_t

    # count raw device->host pulls for one step while prefills overlap
    calls = {"n": 0}
    orig = jax.device_get

    def counting_device_get(x, *a, **kw):
        calls["n"] += 1
        return orig(x, *a, **kw)

    jax.device_get = counting_device_get
    try:
        eng.step()
    finally:
        jax.device_get = orig
    assert len(eng._prefills) >= 1  # the long prefills span several steps
    assert calls["n"] == 1, f"{calls['n']} device->host transfers in a step"
    assert eng.metrics.dispatches == base_d + 1
    eng.run()
    assert all(r.state == "done" for r in eng.finished)
    m = eng.metrics
    assert m.dispatches == m.steps == m.transfers_d2h


def test_unified_requires_paged_and_attention_only(served):
    spec, model, params = served
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(model, params,
                    EngineConfig(max_slots=2, max_seq=64, unified=True))


def test_unified_overlong_prompt_raises_named_error(served):
    """Satellite: a prompt that can never fit max_pages * page_size must
    raise a ValueError naming the request and the capacity — not fail
    inside the kernel index map."""
    spec, model, params = served
    eng = ServeEngine(model, params, _unified_cfg(True, max_seq=32,
                                                  page_size=8))
    with pytest.raises(ValueError, match=r"request 0: .*32 tokens"):
        eng.submit(Request(prompt=list(range(1, 60)), max_new_tokens=4))
    # the pack-time guard fires too (e.g. a resumed request that grew)
    req = Request(prompt=[1, 2, 3], max_new_tokens=4)
    eng.submit(req)
    eng._admit()
    req.output = list(range(40))  # simulate impossible growth
    with pytest.raises(ValueError, match=r"request 1: .*capacity of 32"):
        eng._unified_step()


def test_unified_sampling_smoke(served):
    """Stochastic configs run through the unified sampler (values differ
    from the two-dispatch path's RNG stream, but must be valid)."""
    spec, model, params = served
    eng = ServeEngine(model, params, _unified_cfg(True))
    reqs = eng.serve([
        Request(prompt=[5, 9, 2], max_new_tokens=6),
        Request(prompt=[8, 1, 3], max_new_tokens=6,
                sampling=SamplingConfig(temperature=0.8, top_k=20)),
    ])
    assert reqs[0].output == _greedy_reference(model, params, [5, 9, 2], 6)
    for r in reqs:
        assert r.state == "done" and len(r.output) == 6
        assert all(0 <= t < spec.vocab for t in r.output)


def test_mixed_sampling_configs_one_batch(served):
    """Greedy and stochastic requests share one engine batch; the greedy
    ones still match the reference exactly."""
    spec, model, params = served
    greedy_prompt = [5, 9, 2, 17]
    want = _greedy_reference(model, params, greedy_prompt, 6)
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=3, max_seq=64, chunk_size=4))
    reqs = [Request(prompt=list(greedy_prompt), max_new_tokens=6),
            Request(prompt=[8, 1, 3], max_new_tokens=6,
                    sampling=SamplingConfig(temperature=0.8, top_k=20)),
            Request(prompt=[2, 4, 6, 8], max_new_tokens=6,
                    sampling=SamplingConfig(temperature=1.0, top_p=0.9))]
    eng.serve(reqs)
    assert reqs[0].output == want
    for r in reqs:
        assert r.state == "done" and len(r.output) == 6
        assert all(0 <= t < spec.vocab for t in r.output)


# ---------------------------------------------------------------------------
# debug guards (transfer_guard + retrace assertion)
# ---------------------------------------------------------------------------

def test_debug_guards_unified_matches_guard_off(served):
    """Acceptance: a debug_guards engine completes a mixed prefill+decode
    workload with the transfer guard active, asserts zero steady-state
    retraces, and its greedy outputs are token-identical to guard-off."""
    spec, model, params = served
    prompts = [[5, 9, 2, 17, 33], [7, 7, 7], [42] * 9, [3, 1, 4, 1, 5, 9]]
    outs = {}
    for guards in (False, True):
        eng = ServeEngine(model, params,
                          _unified_cfg(True, debug_guards=guards))
        reqs = eng.serve([Request(prompt=list(p), max_new_tokens=5)
                          for p in prompts])
        assert all(r.state == "done" for r in reqs)
        outs[guards] = [r.output for r in reqs]
    assert outs[True] == outs[False]


def test_debug_guards_two_dispatch_slot_churn(served):
    """The guard + flat-jit-cache assertion must also hold on the
    two-dispatch path across slot churn (requests finishing and new ones
    being admitted re-use slots without retracing)."""
    spec, model, params = served
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=2, max_seq=64, chunk_size=8,
                                   debug_guards=True))
    reqs = [Request(prompt=[1 + i, 2, 3], max_new_tokens=3 + i % 3)
            for i in range(5)]  # > max_slots: forces churn
    eng.serve(reqs)
    assert all(r.state == "done" for r in reqs)
    # the steady-state dispatch traced exactly once and stayed flat
    assert eng._trace_sizes.get("_jit_decode", 0) >= 1


def test_debug_guards_transfer_guard_is_active(served):
    """The guard must actually be armed: an implicit transfer inside the
    step (a numpy array fed straight into a jitted call) has to raise."""
    spec, model, params = served
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=2, max_seq=64, chunk_size=8,
                                   debug_guards=True))
    eng.submit(Request(prompt=[5, 9, 2], max_new_tokens=4))
    while not eng.active:
        eng.step()
    eng.step()  # steady state under the guard: must be clean
    with eng._step_guard():
        with pytest.raises(Exception, match="[Dd]isallow"):
            # an implicit host->device transfer: numpy straight into jit
            jax.jit(lambda x: x + 1)(np.zeros((4,), np.float32))


def test_debug_guards_retrace_assertion_fires(served):
    """_assert_no_retrace must detect a growing jit cache (seeded by
    calling a steady-state dispatchee at a shape the engine never uses)."""
    spec, model, params = served
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=2, max_seq=64, chunk_size=8,
                                   debug_guards=True))
    eng.submit(Request(prompt=[5, 9, 2], max_new_tokens=4))
    while not eng.active:
        eng.step()
    eng.step()
    if getattr(eng._jit_decode, "_cache_size", None) is None:
        pytest.skip("jax version exposes no jit cache introspection")
    # poison the cache: an off-geometry trace of the same jitted callable
    cache2 = model.init_cache(eng.cfg.max_slots, 32, layout="dense")
    eng._jit_decode(params, cache2,
                    jnp.zeros((eng.cfg.max_slots, 1), jnp.int32),
                    jax.random.key(1), jnp.zeros((eng.cfg.max_slots,)),
                    jnp.zeros((eng.cfg.max_slots,), jnp.int32),
                    jnp.ones((eng.cfg.max_slots,)))
    with pytest.raises(AssertionError, match="retrace"):
        eng._assert_no_retrace()
