"""Without a TPU the command exits non-zero and prints no result, and it
does so too in a directory that holds only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

from bench.tests.tiny import REPO


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "minitron-8b-l8.longctx", "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    if not lines:
        return True
    try:
        return not isinstance(json.loads(lines[-1]), dict)
    except ValueError:
        return True


def test_no_tpu_no_result():
    p = run(REPO)
    assert p.returncode != 0 and no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(str(tmp_path))
    assert p.returncode != 0 and no_result(p.stdout)
