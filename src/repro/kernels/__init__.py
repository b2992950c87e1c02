"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel: <name>.py (pl.pallas_call + BlockSpec), a jit'd wrapper in
ops.py, and a pure-jnp oracle in ref.py.  ``backend.py`` compiles them on a
TPU and interprets them on the CPU.  Validated through the interpreter
(tests/test_kernels.py) and compiled for a described v5e
(tests/test_tpu_compile.py).

The secure-aggregation pipeline is fully kernelized: ``shamir_poly``
(share generation + fused fixed-point encode) and ``shamir_reconstruct``
(Lagrange interpolation + CRT Garner digit) cover protect and reveal end
to end over flat (rows, 128) tile buffers — see ``core.secure_agg`` for
the backend switch that routes production traffic through them.
"""
from . import ops, ref, tuning

__all__ = ["ops", "ref", "tuning"]
