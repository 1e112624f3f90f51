"""The trace reduction on a small synthetic trace (data/synthetic.xplane.pb,
made from data/synthetic.xplane.txt), whose answers are worked out by
hand in the text file's header."""

import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(DATA, "synthetic.xplane.pb"))
    return trace.reduce(pd, chips=2)


def test_busy_is_the_union_of_op_intervals_averaged_over_chips(summary):
    assert summary.window_s == pytest.approx(100e-6)
    # chip 0: 10..40 and 60..80 us (the while op encloses its children);
    # chip 1: 10..30 us
    assert summary.busy_s == pytest.approx((50e-6 + 20e-6) / 2)


def test_kernel_time_counts_only_the_custom_calls(summary):
    assert summary.kernel_s == pytest.approx(35e-6)
    assert summary.ops == pytest.approx(
        {"ragged_attn (tpu_custom_call)": 35e-6, "fusion": 15e-6})


def test_step_programs_are_matched_to_host_step_spans(summary):
    assert summary.step_programs == pytest.approx([30e-6, 20e-6])

    class S:
        def __init__(self, mixed):
            self.mixed = mixed
    assert summary.step_ms([S(True), S(False)], mixed=True) == \
        pytest.approx(30e-3)
    assert summary.step_ms([S(True), S(False)], mixed=False) == \
        pytest.approx(20e-3)
    assert summary.step_ms([S(True)], mixed=True) is None  # count differs


def test_idle_gaps_are_credited_to_the_host_span(summary):
    assert summary.idle == pytest.approx(
        {"bench.step": 10e-6, "bench.observe": 20e-6, "bench.wait": 20e-6})
    b = summary.breakdown()
    assert [k for k, _ in b["device_ops"]][0] == \
        "ragged_attn (tpu_custom_call)"
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        summary.window_s - 50e-6)


def test_the_kept_file_is_the_text_form(summary):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "synthetic.xplane.txt")) as f:
        txt = "".join(line for line in f if not line.startswith("#"))
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(txt))
    assert trace.reduce(pd, chips=2) == summary
