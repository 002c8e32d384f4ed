"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the LD_PRELOAD-ordering lesson from the paper,
section 3.1, transposed to JAX: device count locks on first backend init).
Mesh construction goes through :func:`repro.compat.make_mesh` (Auto axis
types).
"""
from __future__ import annotations

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    (DCN-crossing data-parallel) axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 4, pod: int | None = None):
    """Small mesh for subprocess tests (8 fake devices)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def mesh_desc(mesh) -> str:
    return "x".join(f"{mesh.shape[a]}{a[0]}" for a in mesh.axis_names)


def dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
