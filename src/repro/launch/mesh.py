"""Production mesh builders (kept as FUNCTIONS so importing this module never
touches jax device state).

DESIGN.md §3.1 (mesh axes): the production and local mesh builders.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def _auto(n: int) -> tuple:
    """``Auto`` axis types: ``jax.make_mesh`` defaults to ``Explicit`` axes,
    under which the store's replicated gathers need per-op out-shardings."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (v5e); multi_pod adds a leading 2-pod axis.
    The ``pod`` axis composes with ``data`` for all batch/FSDP sharding, so
    scaling pods is a config change, not a code change."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(data: int = 1):
    """CPU-test mesh with the production axis names.  ``data > 1`` (sharded
    store tests) needs ``--xla_force_host_platform_device_count >= data``
    (set in tests/conftest.py before jax backend init)."""
    return jax.make_mesh((data, 1), ("data", "model"), axis_types=_auto(2))
