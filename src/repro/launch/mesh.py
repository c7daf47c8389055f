"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init.
"""

from __future__ import annotations

import jax

from ..distributed.sharding import make_mesh

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis (DCN) for the multi-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests/examples on CPU)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))
