"""Production mesh construction.

A FUNCTION (not a module constant) so importing this module never touches
jax device state — required because the dry-run must set
xla_force_host_platform_device_count before first jax init, while smoke
tests and benches must keep seeing 1 device.

Mesh construction itself lives in repro.compat (``make_mesh`` with every
axis Auto).
"""
from __future__ import annotations

from repro.compat import make_mesh

__all__ = ["make_mesh", "make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
