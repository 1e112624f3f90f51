"""What the engine records of itself: the profiler spans inside
``ServeEngine.step``, the named scopes of the model step (HLO metadata
only), ``Request.admit_t`` and the ``StepRecord`` step log."""

import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.models import build_model
from repro.serving import EngineConfig, Request, ServeEngine
from repro.serving.engine import StepRecord

from conftest import tiny_dense_spec

SCOPES = ("attn", "kv_write", "mlp", "head", "sample")
INNER = ("engine.admit", "engine.pages", "engine.pack", "engine.upload",
         "engine.dispatch", "engine.pull", "engine.commit")


@pytest.fixture(scope="module")
def served():
    model = build_model(tiny_dense_spec(), mesh=None,
                        param_dtype=jnp.float32, compute_dtype=jnp.float32)
    return model, model.init(jax.random.key(3))


def engine(served, mode="unified", **kw):
    model, params = served
    cfg = dict(max_slots=3, max_seq=64, chunk_size=4, prefill_rows=2)
    draft = {}
    if mode != "two_dispatch":
        cfg.update(cache_layout="paged", page_size=8, unified=True)
    if mode == "spec":
        cfg.update(n_spec=2)
        draft = dict(draft_model=model, draft_params=params)
    cfg.update(kw)
    return ServeEngine(model, params, EngineConfig(**cfg),
                       rng=jax.random.key(1), **draft)


def requests():
    return [Request(prompt=list(range(1, n)), max_new_tokens=4)
            for n in (6, 11, 9, 5)]


def step_hlo(served) -> str:
    """The optimised HLO of the mixed unified step (forward + sampling) of
    a tiny engine, with its metadata."""
    model, params = served
    eng = engine(served)
    n, t = eng.n_segs, eng.t_pack
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    f32 = functools.partial(jnp.zeros, dtype=jnp.float32)
    fn = functools.partial(eng._unified_and_sample, max_q=4, n_decode=3)
    return jax.jit(fn).lower(
        params, eng.cache, i32(t), i32(t), i32(n), i32(n), i32(n),
        i32((n, eng.max_pages)), jax.random.key(0), f32(n), i32(n),
        f32(n)).compile().as_text()


@pytest.fixture(scope="module")
def scoped_hlo(served):
    return step_hlo(served)


_DEF = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")


def instructions(text: str) -> list:
    """The HLO's computations and instructions without their metadata,
    names replaced by their order of definition and the parameter names
    of computation signatures dropped (a scope may rename an instruction
    or a ``cond`` branch's parameter; it must not change one)."""
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    lines = [re.sub(r"([(,]\s*)[\w.\-]+: ", r"\1", ln) if _COMP.match(ln)
             else ln for ln in text.splitlines()
             if _DEF.match(ln) or _COMP.match(ln)]
    names: dict = {}
    for ln in lines:
        m = _DEF.match(ln) or _COMP.match(ln)
        names.setdefault(m.group(1), f"v{len(names)}")
    return [re.sub(r"%([\w.\-]+)",
                   lambda m: "%" + names.get(m.group(1), m.group(1)), ln)
            for ln in lines]


@pytest.mark.parametrize("scope", SCOPES)
def test_the_step_hlo_carries_the_scope(scoped_hlo, scope):
    names = re.findall(r'op_name="([^"]*)"', scoped_hlo)
    assert any(scope in n.split("/") for n in names), scope


def test_the_scopes_change_nothing_but_metadata(served, scoped_hlo,
                                                monkeypatch):
    import contextlib
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = step_hlo(served)
    assert not any("attn" in n.split("/") for n in
                   re.findall(r'op_name="([^"]*)"', bare))
    assert instructions(bare) == instructions(scoped_hlo)
    assert len(instructions(bare)) > 100


def host_spans(path):
    from jax.profiler import ProfileData
    pb, = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("engine.")]
    return sorted(out)


@pytest.mark.parametrize("mode", ["unified", "spec", "two_dispatch"])
def test_one_engine_step_span_per_step_holding_the_phases(served, tmp_path,
                                                          mode):
    eng = engine(served, mode)
    for r in requests():
        eng.submit(r)
    eng.step()  # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            eng.step()
    spans = host_spans(str(tmp_path))
    steps = [s for s in spans if s[2] == "engine.step"]
    assert [s[3]["step"] for s in steps] == [2, 3, 4, 5]
    inner = [s for s in spans if s[2] != "engine.step"]
    for s0, s1, name, _ in inner:
        assert sum(a <= s0 and s1 <= b for a, b, _, _ in steps) == 1, name
    want = INNER if mode != "two_dispatch" else ("engine.admit",)
    for a, b, _, _ in steps:  # every phase once per step, in order
        names = [n for s0, _, n, _ in inner if a <= s0 < b]
        assert tuple(names) == want


def test_step_records_and_admission_times(served):
    eng = engine(served, record_step_log=True)
    reqs = eng.serve(requests())
    log = eng.metrics.step_log
    assert all(isinstance(r, StepRecord) for r in log)
    assert [r.step for r in log] == list(range(1, eng.steps + 1))
    assert all(r.t0 <= r.t1 for r in log)
    admitted = [rid for r in log for rid in r.admitted]
    assert sorted(admitted) == sorted(r.rid for r in reqs)
    for r in reqs:
        assert r.submit_t <= r.admit_t <= r.first_token_t
    for r in log:
        live = len(r.decode) + sum(q for q, _ in r.prefill)
        assert r.rows_live == live <= r.rows_packed
        assert r.rows_packed == (eng.t_pack if r.mixed
                                 else eng.cfg.max_slots)
        assert r.mixed == bool(r.prefill)
        assert r.pages_in_use >= 0 and r.preempted == 0
    # every output token was sampled from a recorded segment
    assert sum(r.sampled for r in log) == sum(len(r.output) for r in reqs)
