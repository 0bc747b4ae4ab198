"""Production mesh construction (assignment spec).

Kept as functions — importing this module never touches jax device state.
"""
from __future__ import annotations

from ..distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis
    extends data parallelism across the DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke tests."""
    return make_mesh((1, 1), ("data", "model"))
