"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Fake host devices for the 16x16 / 2x16x16
production meshes come from ``repro.runtime_config.fake_devices(512)``
(the dry-run entrypoint calls it before importing jax) — that module is
the ONE place ``xla_force_host_platform_device_count`` is spelled;
setting ``XLA_FLAGS`` by hand here or in callers is deprecated because a
bare assignment clobbers whatever flags the launcher already exported.
Smoke tests and benchmarks see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the models annotate shardings and let the partitioner
    # propagate them (Explicit axes, jax.make_mesh's default, would demand
    # matching operand shardings at every dynamic_update_slice)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for single-device smoke runs."""
    return _auto_mesh((1, 1), ("data", "model"))
