"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked at first jax init, and
smoke tests / benches must see 1 device while the dry-run sees 512).
"""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    # Auto axes keep GSPMD auto-propagation (make_mesh defaults to
    # Explicit).
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data×model single-pod; (2,16,16) pod×data×model multi-pod.

    256 chips per pod (TPU v5e-256); the multi-pod mesh proves the "pod"
    axis shards (cross-pod = DCN data parallelism, see DESIGN.md §6).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh helper for tests/benchmarks."""
    return _mesh(tuple(shape), tuple(axes))
