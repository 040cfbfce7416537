"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Tiny mesh for CPU integration tests (requires
    xla_force_host_platform_device_count >= n_data*n_model)."""
    return make_mesh((n_data, n_model), ("data", "model"))
