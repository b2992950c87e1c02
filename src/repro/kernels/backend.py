"""Whether the Pallas kernels compile or interpret, decided once from JAX's
backend.

The kernels are written for the TPU: Mosaic compiles them there.  On the
CPU (tests, laptops) the Pallas interpreter runs the same kernel bodies.
No other backend is supported, and no caller chooses: a hand-threaded
``interpret=True`` that survives onto a TPU would quietly run every
protocol kernel through the interpreter and hide the device from every
measurement.
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["ZERO", "interpret_kernels", "resolve_interpret"]

# The zero block index for BlockSpec index maps.  Under the process-wide
# x64 mode a bare Python 0 traces as i64, which Mosaic cannot legalize.
ZERO = np.int32(0)


def interpret_kernels() -> bool:
    """True on ``cpu`` (Pallas interpreter), False on ``tpu`` (compiled).

    Raises ``RuntimeError`` on any other backend.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"JAX's default backend is {backend!r}"
    )


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit ``interpret`` (tests, ahead-of-time compiles for a
    described chip) wins; ``None`` follows the backend."""
    return interpret_kernels() if interpret is None else bool(interpret)
