"""A tiny benchmark root for CPU tests: its own BENCHMARK.json, one small
configuration, a closed and an open mix, and the real metric readers.
Runs go through ``bench.harness.run_cell`` with the chip look and the
peaks table stood in for (``devices``, ``peak``)."""

from __future__ import annotations

import json
import os
import shutil
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

CONFIG = {"hidden_size": 128, "num_hidden_layers": 2,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 32, "intermediate_size": 256, "hidden_act": "relu2",
          "vocab_size": 2048, "tie_word_embeddings": False,
          "rope_theta": 10000.0, "partial_rotary_factor": 1.0,
          "norm_eps": 1e-6, "serving": {"tp": 1},
          # program readings on the CPU 0.001-0.003, the fp8 control's
          # 0.043-0.081 (seeds 1-4)
          "correct": {"max_logit_gap": 0.015}}
ENGINE = {"max_slots": 4, "max_seq": 96, "chunk_size": 16,
          "prefill_rows": 2, "n_pages": 25, "page_size": 16}
CLOSED = {"loop": "closed", "clients": 4, "fill_before_window": True,
          "prompt": {"dist": "uniform", "lo": 20, "hi": 40},
          "output": {"dist": "uniform", "lo": 16, "hi": 40}, "block": 4,
          "engine": ENGINE, "check": {"requests": 4}}
OPEN = {"loop": "open", "rate_req_s": 3.0, "burst_factor": 4.0,
        "on_s": 0.5, "off_s": 0.5, "arrival_seed": 7, "preroll_s": 0.5,
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                   "lo": 8, "hi": 40},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "lo": 2, "hi": 24}, "block": 4, "engine": ENGINE,
        "check": {"requests": 2}}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}


def make_root(tmp: str) -> str:
    """A root holding BENCHMARK.json and bench/{configs,traffic,metrics}
    for the cells ``tiny.closed`` and ``tiny.open``."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU tests"}]
    bench["workloads"] = [
        {"name": "tiny.closed", "config": "tiny", "traffic": "tclosed",
         "chips": 1, "why": "CPU tests"},
        {"name": "tiny.open", "config": "tiny", "traffic": "topen",
         "chips": 1, "why": "CPU tests"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.open"]
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(tmp, "bench", sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(tmp, "bench", "metrics"))
    for path, doc in (("BENCHMARK.json", bench),
                      ("bench/configs/tiny.json", CONFIG),
                      ("bench/traffic/tclosed.json", CLOSED),
                      ("bench/traffic/topen.json", OPEN)):
        with open(os.path.join(tmp, path), "w") as f:
            json.dump(doc, f)
    return tmp


def run(root: str, cell: str, seed: int, seconds: float = 1.5,
        control: bool = False) -> dict:
    import jax
    from bench.harness import run_cell
    return run_cell(root, cell, seed, seconds, False, time.perf_counter(),
                    devices=jax.devices(), peak=PEAK, control=control)
