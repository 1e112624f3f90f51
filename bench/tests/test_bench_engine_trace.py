"""The engine's spans and the model step's scopes in a trace
(data/engine.xplane.pb, made from data/engine.xplane.txt, whose answers
are worked out by hand in the text file's header), read by
bench/engine_trace beside bench/trace, and the admission metric that
reads the engine's own stamp."""

import os
from types import SimpleNamespace

import pytest

from bench import engine_trace, harness, trace
from bench.tests import tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1e-6


def profile(name):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, name), "rb") as f:
        raw = f.read()
    return ProfileData.from_serialized_xspace(raw), raw


@pytest.fixture(scope="module")
def summary():
    return engine_trace.reduce(*profile("engine.xplane.pb"))


@pytest.fixture(scope="module")
def harness_summary():
    return trace.reduce(profile("engine.xplane.pb")[0], 1)


def test_device_time_by_outermost_scope(summary, harness_summary):
    assert summary.scopes == pytest.approx(
        {"attn": 24 * US, "mlp": 6 * US, "head": 5 * US, "sample": 8 * US,
         "other": 15 * US})
    assert summary.kv_write_s == pytest.approx(4 * US)
    # the scopes split the same leaf ops the per-op labels do
    assert sum(summary.scopes.values()) == pytest.approx(
        sum(harness_summary.ops.values()))
    assert sum(summary.scopes.values()) == pytest.approx(
        harness_summary.busy_s)
    assert summary.window_s == pytest.approx(harness_summary.window_s)


def test_idle_is_credited_to_the_innermost_engine_span(summary,
                                                        harness_summary):
    assert summary.engine_idle == pytest.approx(
        {"engine.upload": 22 * US, "engine.pull": 5 * US,
         "engine.commit": 15 * US})
    # the harness's spans are credited as before
    assert harness_summary.idle == pytest.approx(
        {"bench.step": 27 * US, "bench.wait": 15 * US})


def test_engine_spans_and_their_step_numbers(summary):
    assert summary.engine == pytest.approx(
        {"engine.step": 82 * US, "engine.admit": 1.5 * US,
         "engine.pages": 1 * US, "engine.pack": 4.5 * US,
         "engine.upload": 6 * US, "engine.dispatch": 2 * US,
         "engine.pull": 61 * US, "engine.commit": 5 * US})
    assert summary.engine_steps == [7, 8]
    slow = summary.engine_slowest
    assert slow["step"] == 7 and slow["s"] == pytest.approx(56 * US)
    assert slow["spans"]["engine.pull"] == pytest.approx(45.5 * US)


def test_existing_fields_read_the_renamed_kernel(harness_summary):
    assert harness_summary.kernel_s == pytest.approx(10 * US)
    assert harness_summary.step_programs == pytest.approx([48 * US,
                                                           15 * US])
    assert harness_summary.ops["ragged_attn (tpu_custom_call)"] == \
        pytest.approx(10 * US)


def test_the_kept_file_is_the_text_form(summary):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "engine.xplane.txt")) as f:
        txt = "".join(line for line in f if not line.startswith("#"))
    raw = ProfileData.text_proto_to_serialized_xspace(txt)
    assert engine_trace.reduce(ProfileData.from_serialized_xspace(raw),
                               raw) == summary


def test_without_the_raw_trace_nothing_is_split_by_scope():
    s = engine_trace.reduce(profile("engine.xplane.pb")[0])
    assert s.scopes == {} and s.engine_steps == [7, 8]


def test_reduce_dir_finds_the_one_trace(tmp_path, summary):
    import shutil
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "engine.xplane.pb"), d / "h.xplane.pb")
    assert engine_trace.reduce_dir(str(tmp_path)) == summary


def run_of(admit=True, submit=1.0):
    eng = SimpleNamespace(submit_t=submit)
    if admit is not None:
        eng.admit_t = admit
    req = SimpleNamespace(due=1.0, submit=1.0, engine=eng)
    return SimpleNamespace(reqs=[req], t0=0.0, t_end=10.0, window_end=10.5)


def admit_ms(run):
    return harness.metric_reader(tiny.REPO, "sched.admit_ms_p95")(run)


def test_admission_wait_reads_the_engine_stamp():
    assert admit_ms(run_of(admit=1.2)) == pytest.approx(200.0)
    # one not admitted by the window's end counts at the end
    assert admit_ms(run_of(admit=0.0)) == pytest.approx(9500.0)
    assert admit_ms(run_of(admit=11.0)) == pytest.approx(9500.0)


@pytest.mark.parametrize("what", ["sched.admit_ms_p95", "scopes", "engine",
                                  "engine_idle", "engine_steps",
                                  "engine_slowest"])
def test_a_program_without_spans_or_records_reads_nothing(what):
    """A program that opens no engine span and no scope and stamps no
    admission (an older engine) gives the admission metric no value, and
    the engine reduction of its trace (data/synthetic.xplane.pb) nothing,
    and no error."""
    if what == "sched.admit_ms_p95":
        assert admit_ms(run_of(admit=None)) is None
        return
    s = engine_trace.reduce(*profile("synthetic.xplane.pb"))
    assert not getattr(s, what)
