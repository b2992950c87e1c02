"""XLA_FLAGS management that is safe against jax's one-shot flag read.

XLA reads ``XLA_FLAGS`` exactly once, when the first backend client is
created (the first ``jax.devices()`` / first trace / first array op) — a
later assignment to ``os.environ`` silently does nothing.  Before this
helper existed, ``launch/dryrun.py`` set the variable twice (a module-level
default on line 2 and an arg-driven overwrite after ``parse_args``), which
worked only by the accident that nothing between the two had touched a
backend.  Every flag writer now routes through :func:`apply_xla_flags`,
which *verifies* no jax backend exists yet and raises instead of silently
losing the flag.

This module must therefore import WITHOUT importing jax (merely importing
jax is fine — flags are read at backend init, not at import — but pulling
in ``distributed.sharding`` would create arrays).  ``repro.distributed``'s
``__init__`` is lazy for exactly this reason.

Typical uses::

    from repro.distributed.xla_flags import apply_xla_flags
    apply_xla_flags(host_device_count=8)        # before first jax use
    import jax                                   # sees 8 CPU devices

    # subprocess workers (CPU-mesh CI): build the child env instead;
    # latency_hiding=True only when the child targets a GPU backend
    env = mesh_env(host_device_count=256)
    subprocess.run([...], env=env)
"""
from __future__ import annotations

import os
import sys

__all__ = [
    "LATENCY_HIDING_FLAGS",
    "apply_xla_flags",
    "jax_backend_initialized",
    "mesh_env",
]

# The collective-overlap knobs from the olmax run scripts (SNIPPETS.md):
# async collectives let a reduction proceed while independent work (the
# next chunk's encode+share) issues; the latency-hiding scheduler orders
# the HLO so that independent work actually lands between a collective's
# start and done.  GPU-ONLY: XLA's flag parser hard-aborts the process on
# flags the build does not know (``parse_flags_from_env.cc``), and
# CPU-only builds do not register the ``--xla_gpu_*`` family — so these
# are requested explicitly by GPU launch paths (``latency_hiding=True``)
# and must stay OFF for the forced-host-device CPU meshes CI runs.
LATENCY_HIDING_FLAGS: tuple[str, ...] = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def jax_backend_initialized() -> bool:
    """True once any XLA backend client exists (flags are locked in).

    Checks the live interpreter state rather than "is jax imported":
    importing jax does not read XLA_FLAGS; creating the first backend
    does.  Probes the private backend cache without triggering backend
    creation (calling any public device API would itself lock the flags).
    """
    if "jax" not in sys.modules:
        return False
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None:
        return False
    return bool(getattr(xb, "_backends", None))


def _merge_flags(existing: str, updates: list[str]) -> str:
    """Merge flag strings, last-writer-wins per flag name."""
    out: dict[str, str] = {}
    order: list[str] = []
    for tok in existing.split() + updates:
        name = tok.split("=", 1)[0]
        if name not in out:
            order.append(name)
        out[name] = tok
    return " ".join(out[name] for name in order)


def _build(host_device_count: int | None, latency_hiding: bool,
           extra: tuple[str, ...] | list[str], existing: str) -> str:
    updates: list[str] = []
    if host_device_count is not None:
        if host_device_count < 1:
            raise ValueError("host_device_count must be >= 1")
        updates.append(
            f"--xla_force_host_platform_device_count={host_device_count}"
        )
    if latency_hiding:
        updates.extend(LATENCY_HIDING_FLAGS)
    updates.extend(extra)
    return _merge_flags(existing, updates)


def apply_xla_flags(
    host_device_count: int | None = None,
    latency_hiding: bool = False,
    extra: tuple[str, ...] | list[str] = (),
) -> str:
    """Set ``os.environ["XLA_FLAGS"]`` — verified to land before jax init.

    Merges into any flags already present (per-flag, last writer wins, so
    re-applying the same value is idempotent).  Raises ``RuntimeError``
    if a jax backend already exists and the merge would CHANGE the flag
    string — the change could never take effect, and the silent version
    of that bug is exactly what this helper retires.  Returns the final
    flag string.
    """
    existing = os.environ.get("XLA_FLAGS", "")
    merged = _build(host_device_count, latency_hiding, extra, existing)
    if merged != existing and jax_backend_initialized():
        raise RuntimeError(
            "XLA backend already initialized; XLA_FLAGS changes can no "
            f"longer take effect (wanted {merged!r}, locked at "
            f"{existing!r}).  Apply flags before the first jax device/"
            "array operation — e.g. at process start, or spawn a "
            "subprocess with mesh_env()."
        )
    os.environ["XLA_FLAGS"] = merged
    return merged


def mesh_env(
    host_device_count: int | None = None,
    latency_hiding: bool = False,
    extra: tuple[str, ...] | list[str] = (),
    base: dict | None = None,
) -> dict:
    """A child-process environment with the merged XLA_FLAGS.

    The subprocess-launch twin of :func:`apply_xla_flags`: never touches
    this process's environment (so the parent's already-initialized jax
    is irrelevant), which is how the CPU-mesh CI jobs and the multihost
    benchmark give each worker its own forced device count.  A forced
    host device count is a CPU mesh, so the child is also held to the
    CPU backend: an accelerator belongs to one process, and a parent
    that touched JAX already holds it.
    """
    env = dict(os.environ if base is None else base)
    env["XLA_FLAGS"] = _build(
        host_device_count, latency_hiding, extra, env.get("XLA_FLAGS", "")
    )
    if host_device_count is not None:
        env["JAX_PLATFORMS"] = "cpu"
    return env
