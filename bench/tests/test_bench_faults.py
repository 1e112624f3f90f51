"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference at fp8) fails the limit while the program
passes it.  A tiny cell on the CPU, through the harness's whole run but
for its look for a chip."""

import jax.numpy as jnp
import pytest

from bench.tests import tiny
from repro.serving import engine as engine_mod

ORIG = engine_mod.ServeEngine._unified_and_sample


def state_unchanged(self, params, cache, *a, **kw):
    toks, feed, _ = ORIG(self, params, cache, *a, **kw)
    return toks, feed, cache  # the step's KV writes are dropped


def token_altered(self, params, cache, *a, **kw):
    toks, _, new = ORIG(self, params, cache, *a, **kw)
    toks = (toks + 1) % self.model.spec.vocab
    return toks, toks[:self.cfg.max_slots], new


def half_batch(self, params, cache, *a, **kw):
    toks, _, new = ORIG(self, params, cache, *a, **kw)
    n = self.cfg.max_slots
    h = n // 2  # the upper half of the decode slots gets the lower half's
    toks = jnp.concatenate([toks[:h], toks[:n - h], toks[n:]])
    return toks, toks[:n], new


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    import repro.launch.runtime as rt
    monkeypatch.setattr(rt, "use_compile_cache", lambda root: "off")


@pytest.mark.parametrize("fault", [state_unchanged, token_altered,
                                   half_batch])
def test_a_broken_step_is_not_correct(root, monkeypatch, fault):
    monkeypatch.setattr(engine_mod.ServeEngine, "_unified_and_sample",
                        fault)
    out = tiny.run(root, "tiny.closed", seed=11)
    gap = out["check"]["max_logit_gap"]
    assert out["correct"] is False and gap["value"] > gap["limit"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_control_fails_and_the_program_passes(root, seed):
    out = tiny.run(root, "tiny.closed", seed=seed, control=True)
    gap = out["check"]["max_logit_gap"]
    assert out["correct"] is True and gap["value"] < gap["limit"]
    assert out["control_gap"] > gap["limit"]
