"""Production mesh construction (assignment: MULTI-POD DRY-RUN step 1).

Defined as functions (never module-level constants) so importing this
module never touches jax device state. Mesh construction goes through
core/compat.py, like every mesh in the repo.
"""
from __future__ import annotations

from repro.core.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading
    'pod' axis (DCN-connected)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small host-device mesh for CPU integration tests."""
    return make_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple[str, ...]:
    """Data-parallel axes: every mesh axis except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def flat_axes_of(mesh) -> tuple[str, ...]:
    """All axes — the sort/shuffle treats every chip as a worker."""
    return tuple(mesh.axis_names)
