"""Process set-up of the entry points: the chip smoke refuses the CPU, the
compile cache lives where it should, ``--devices`` fakes devices only on
the CPU, and a process holding an accelerator never forks sweep workers."""

import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.launch import runtime
from repro.scenario import runner

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """On the CPU, and as a lone file outside the repo, the smoke exits
    non-zero and prints no result line."""
    if alone:
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        cwd, script = str(tmp_path), "chip_smoke.py"
    else:
        cwd, script = ROOT, os.path.join(ROOT, "chip_smoke.py")
    out = _run([script], cwd)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    if not alone:
        assert "no TPU" in out.stderr


_COMPILE = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    from repro.launch.runtime import use_compile_cache
    print(use_compile_cache(sys.argv[1]))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(7)).block_until_ready()
""")


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_directory(tmp_path, env_set):
    """``JAX_COMPILATION_CACHE_DIR`` wins where set; otherwise the cache
    sits at the fixed ``<root>/.jax_cache`` and nowhere else."""
    root, elsewhere = tmp_path / "root", tmp_path / "env_cache"
    root.mkdir()
    env = {"PYTHONPATH": os.path.join(ROOT, "src")}
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(elsewhere)
    out = _run(["-c", _COMPILE, str(root)], str(tmp_path), **env)
    assert out.returncode == 0, out.stderr
    fixed = root / runtime.CACHE_SUBDIR
    used, unused = (elsewhere, fixed) if env_set else (fixed, elsewhere)
    assert out.stdout.strip() == str(used)
    assert used.is_dir() and any(used.iterdir())
    assert not unused.exists()


@pytest.mark.parametrize("platforms,forced", [("cpu", True), ("tpu", False),
                                              ("", False)])
def test_devices_flag_fakes_only_cpu_devices(monkeypatch, platforms, forced):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=x")
    runtime.force_host_devices(["serve", "--devices", "4"])
    flags = os.environ["XLA_FLAGS"]
    assert ("--xla_force_host_platform_device_count=4" in flags) == forced
    assert "--xla_dump_to=x" in flags


def test_sweep_never_forks_from_a_process_holding_a_chip(monkeypatch):
    from repro.scenario import Scenario, Sweep
    assert not runner._holds_accelerator()  # the CPU holds no chip
    monkeypatch.setattr(runner, "_holds_accelerator", lambda: True)

    def no_pool(workers):
        raise AssertionError("forked a worker pool")
    monkeypatch.setattr(runner, "_get_pool", no_pool)
    base = Scenario.make("llama3-8b", use_case="chat", batch=4,
                         platform="hgx-h100x8", parallelism=dict(tp=8))
    scs = Sweep(base).over(batch=[1, 2, 4, 8, 16, 32, 64, 128]).scenarios()
    reports = runner.run(scs, max_workers=4)
    assert [r.scenario for r in reports] == scs
