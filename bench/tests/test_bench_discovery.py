"""The harness finds a configuration, a traffic mix and a metric by the
name BENCHMARK.json gives it: adding a cell or a metric is adding files
and entries, with no edit to the harness."""

import json
import os

import pytest

from bench import harness
from bench.tests import tiny

READER = '''"""Requests the window finished."""


def read(run):
    return float(sum(1 for r in run.reqs
                     if r.done_t is not None and r.done_t >= run.t0))
'''


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    import repro.launch.runtime as rt
    monkeypatch.setattr(rt, "use_compile_cache", lambda root: "off")


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tiny.make_root(str(tmp_path))
    # a further configuration, mix and end-to-end metric: files + entries
    cfg = dict(tiny.CONFIG, num_hidden_layers=1, intermediate_size=192)
    mix = dict(tiny.CLOSED, clients=2, prompt={"dist": "uniform", "lo": 8,
                                               "hi": 16})
    with open(tmp_path / "bench/configs/tiny1.json", "w") as f:
        json.dump(cfg, f)
    with open(tmp_path / "bench/traffic/short.json", "w") as f:
        json.dump(mix, f)
    with open(tmp_path / "bench/metrics/requests_done.py", "w") as f:
        f.write(READER)
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny1", "source": "test",
                             "file": "bench/configs/tiny1.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny1.short", "config": "tiny1",
                               "traffic": "short", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "requests_done", "unit": "count",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["tiny1.short"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    cell = harness.load_cell(root, "tiny1.short")
    assert cell["config"]["num_hidden_layers"] == 1
    assert cell["mix"]["clients"] == 2
    assert "requests_done" in [m["name"] for m in cell["end_to_end"]]
    assert "ttft_p95_ms" not in [m["name"] for m in cell["end_to_end"]]
    out = tiny.run(root, "tiny1.short", seed=3)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"out_tok_s_per_chip", "itl_p95_ms",
                                   "setup_s", "requests_done"}
    assert out["metrics"]["requests_done"]["value"] >= 1


def test_every_metric_of_the_benchmark_has_a_reader():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.metric_reader(tiny.REPO, m["name"]))
    for w in bench["workloads"]:
        cell = harness.load_cell(tiny.REPO, w["name"])
        assert cell["mix"]["engine"]["max_slots"] >= 1


def test_open_loop_cell_reports_its_tail(tmp_path):
    root = tiny.make_root(str(tmp_path))
    out = tiny.run(root, "tiny.open", seed=5, seconds=2.0)
    assert out["correct"] is True
    assert {"out_tok_s_per_chip", "itl_p95_ms", "ttft_p95_ms",
            "setup_s"} == set(out["metrics"])
    assert list(out)[-1] == "check"
