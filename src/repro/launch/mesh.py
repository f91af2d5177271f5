"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state. Single pod: 16x16 = 256 chips (TPU v5e pod slice), axes
(data, model). Multi-pod: 2 pods = 512 chips, axes (pod, data, model); the
'pod' axis carries either data parallelism (default) or the GPipe pipeline
(parallel/pipeline.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_devices: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
