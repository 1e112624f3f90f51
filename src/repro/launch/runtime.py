"""Process set-up shared by the entry points (``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train``,
``benchmarks/serving_bench.py``).  The library itself never calls these:
importing :mod:`repro` changes no JAX setting.
"""

from __future__ import annotations

import os

#: the compile cache's fixed home inside the checkout (git-ignored); a
#: cache directory that moves between runs never hits
CACHE_SUBDIR = ".jax_cache"


def force_host_devices(argv: list[str]) -> None:
    """``--devices N`` under ``JAX_PLATFORMS=cpu``: give the host platform
    N virtual devices.  Must run before JAX starts a backend.  Anywhere
    else the flag is left alone and the real devices are used."""
    if "--devices" in argv \
            and os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        n = argv[argv.index("--devices") + 1]
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} "
            + os.environ.get("XLA_FLAGS", ""))


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and is
    left as it is; otherwise the cache lives at ``<root>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), CACHE_SUBDIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
