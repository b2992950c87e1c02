"""Distributed execution: meshes, sharding rules, multi-host launch.

Lazy re-exports (PEP 562): ``xla_flags`` must be importable WITHOUT
importing jax — it has to run before the first backend init to do its
job — but ``sharding``/``multihost`` import jax at module level.  Attribute
access resolves the submodule on first use, so
``from repro.distributed.xla_flags import apply_xla_flags`` stays
jax-free while ``from repro.distributed import MeshRules`` keeps
working unchanged.
"""
from __future__ import annotations

__all__ = ["MeshRules", "POD_AXIS", "SHARE_AXIS",
           "initialize_distributed", "make_mesh", "param_pspec",
           "param_shardings", "pod_mesh", "pod_share_mesh",
           "run_scanned_rounds", "scan_secure_rounds", "secure_psum_2d"]

_SHARDING = ("MeshRules", "POD_AXIS", "make_mesh", "param_pspec",
             "param_shardings")
_MULTIHOST = ("SHARE_AXIS", "initialize_distributed", "pod_mesh",
              "pod_share_mesh", "run_scanned_rounds", "scan_secure_rounds",
              "secure_psum_2d")


def __getattr__(name: str):
    if name in _SHARDING:
        from . import sharding
        return getattr(sharding, name)
    if name in _MULTIHOST:
        from . import multihost
        return getattr(multihost, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(__all__)
