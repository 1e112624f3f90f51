"""The open loop's time to first token splits without overlap: the load
generator's lag (due -> submit), the scheduler's queue (submit -> first
step that packs the request), then the request's own steps."""

from types import SimpleNamespace

import pytest

from bench import harness
from bench.tests import tiny


def reader(name):
    return harness.metric_reader(tiny.REPO, name)


def one(due, submit, packed, first):
    req = SimpleNamespace(due=due, submit=submit, packed=packed,
                          token_t=[first] if first is not None else [])
    return SimpleNamespace(reqs=[req], t0=0.0, t_end=10.0, window_end=10.5)


@pytest.mark.parametrize("due,submit,packed,first", [
    (1.0, 1.2, 1.25, 1.6),  # packed by the first step after its submit
    (2.0, 2.0, 3.5, 4.0),  # queued for steps before it was packed
])
def test_entry_and_scheduler_split_the_time_to_first_token(due, submit,
                                                           packed, first):
    run = one(due, submit, packed, first)
    lag = reader("entry.submit_lag_ms_p95")(run)
    queue = reader("sched.queue_ms_p95")(run)
    ttft = reader("ttft_p95_ms")(run)
    assert lag == pytest.approx(1e3 * (submit - due))
    assert queue == pytest.approx(1e3 * (packed - submit))
    assert ttft == pytest.approx(lag + queue + 1e3 * (first - packed))


def test_a_request_never_packed_waits_until_the_window_ends():
    run = one(9.0, 9.1, None, None)
    assert reader("sched.queue_ms_p95")(run) == pytest.approx(1e3 * 1.4)
    assert reader("ttft_p95_ms")(run) == pytest.approx(1e3 * 1.5)
