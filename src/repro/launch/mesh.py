"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first init.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ("data", "model") — 256 chips.
    Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (forced host devices)."""
    return _make_mesh(shape, axes)


def make_mesh_for(n_devices: int):
    """Best-effort (data, model) mesh over whatever devices exist."""
    model = 1
    for cand in (16, 8, 4, 2, 1):
        if n_devices % cand == 0:
            model = cand
            break
    return make_test_mesh((n_devices // model, model), ("data", "model"))
