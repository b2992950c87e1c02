"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (important: smoke tests must see 1 CPU device;
only dryrun.py forces 512 placeholder devices via XLA_FLAGS).
"""
from __future__ import annotations

from ..distributed.sharding import make_mesh

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    The paper's institutions map onto the "pod" axis (one institution = one
    pod); "model" carries TP/EP/sequence-sharded KV.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    from ..distributed.sharding import POD_AXIS

    axes = (POD_AXIS, "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(axes=("data", "model")):
    """1x1 mesh over the single local device (smoke tests, examples)."""
    shape = (1,) * len(axes)
    return make_mesh(shape, axes)
