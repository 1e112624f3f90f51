"""Production mesh construction.

Single pod: 256 TPU-v5e chips as a (data=16, model=16) mesh — TP/EP on the
innermost 16-chip ICI ring (the paper's "TP NPUs physically closest" order),
DP/FSDP across the other axis.  Multi-pod: 2 pods = 512 chips with a leading
"pod" axis over the slower inter-pod DCN, used for data parallelism (or
pipeline stages via ``repro.training.pipeline``).

Every axis is ``AxisType.Auto``: sharding is propagated by the compiler,
never checked against explicit types.

This module never touches jax device state at import time; meshes are built
inside functions so the dry-run's ``xla_force_host_platform_device_count``
trick stays confined to ``dryrun.py``.
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small runs)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int | None = None):
    """Mesh over whatever devices exist (e.g. 1 CPU, or N fake devices)."""
    n = len(jax.devices())
    model = model or 1
    data = n // model
    return make_mesh((data, model), ("data", "model"))
