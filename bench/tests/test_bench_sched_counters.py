"""The scheduler's metrics two ways from one traced run of a tiny cell:
reconstructed by the harness from the engine's state around each step
(``sched.pad_pct``, ``sched.queue_ms_p95``) and recorded by the engine
itself (its StepRecords, ``sched.admit_ms_p95`` from ``Request.admit_t``).
The harness does not turn the engine's step records on, so the tiny
cells' engine does here.  On the CPU there is no device trace to reduce,
so the reduction is stood in for."""

import json
import os

import pytest

from bench import harness
from bench import trace as btrace
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))
    for mix in ("tclosed", "topen"):
        path = os.path.join(root, "bench", "traffic", mix + ".json")
        with open(path) as f:
            doc = json.load(f)
        doc["engine"]["record_step_log"] = True
        with open(path, "w") as f:
            json.dump(doc, f)
    return root


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    import repro.launch.runtime as rt
    monkeypatch.setattr(rt, "use_compile_cache", lambda root: "off")


def traced(root, monkeypatch, cell, seed):
    runs, engines = [], []
    report, build = harness.report_lines, harness.build

    def keep(run):
        runs.append(run)
        report(run)

    def keep_engine(cell, seed):
        eng, mix = build(cell, seed)
        engines.append(eng)
        return eng, mix
    monkeypatch.setattr(harness, "report_lines", keep)
    monkeypatch.setattr(harness, "build", keep_engine)
    monkeypatch.setattr(btrace, "reduce_dir", lambda path, chips:
                        btrace.Summary(window_s=1.0, busy_s=1.0,
                                       kernel_s=0.0))
    import jax
    import time
    out = harness.run_cell(root, cell, seed, 1.5, True, time.perf_counter(),
                           devices=jax.devices(), peak=tiny.PEAK)
    run = runs[0]
    records = [r for r in engines[0].metrics.step_log if r.t0 >= run.t0]
    return out, run, records


def read(root, name, run):
    return harness.metric_reader(root, name)(run)


@pytest.mark.parametrize("cell", ["tiny.closed", "tiny.open"])
def test_both_versions_of_the_scheduler_metrics_agree(root, monkeypatch,
                                                      cell):
    out, run, records = traced(root, monkeypatch, cell, seed=2**33 + 7)
    assert out["correct"] is True
    # the engine recorded exactly the window's steps
    assert len(records) == len(run.steps) > 0
    assert [r.mixed for r in records] == [s.mixed for s in run.steps]
    assert [r.rows_packed for r in records] == [s.t_pack for s in run.steps]
    assert [r.sampled for r in records] == [s.sampled for s in run.steps]
    assert [r.pages_in_use for r in records] == [s.pages for s in run.steps]
    pad = read(root, "sched.pad_pct", run)
    rows = sum(r.rows_packed for r in records)
    live = sum(r.rows_live for r in records)
    assert 100.0 * (1.0 - live / rows) == pytest.approx(pad)
    queue = read(root, "sched.queue_ms_p95", run)
    admit = read(root, "sched.admit_ms_p95", run)
    one_step = 1e3 * max(s.t1 - s.t0 for s in run.steps)
    assert queue is not None and admit is not None
    assert abs(admit - queue) <= one_step
