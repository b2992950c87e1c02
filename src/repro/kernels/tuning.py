"""Block-size knobs for the ``interpret=False`` (compiled) kernel path.

CPU CI always runs the Pallas kernels through the interpreter, where block
sizes are semantically irrelevant (the interpreter materializes whole
operands).  On a real TPU backend the same ``block_n`` / ``block_rows``
statics decide the per-grid-step VMEM working set — a bad knob fails at
compile time with an opaque allocation error, long after the benchmark
has burned its setup work.

This module makes the knobs *inspectable*: one record per kernel family
with the default blocking the code actually uses, and a pure-arithmetic
working-set model (`vmem_bytes`) so ``validate_real_kernel_knobs`` can
reject a configuration BEFORE any compilation is attempted.  The
benchmarks expose it behind ``--real-kernels``; on the CPU CI mesh the
validation still runs (it is just arithmetic) and the flag is otherwise
a documented no-op — nothing about the interpreted kernels changes.

The model intentionally over-counts slightly (inputs + outputs resident
simultaneously, no double-buffering discount), so a passing knob has
real headroom.  It is a pre-flight estimate, not the authority: the TPU
compiler is, and ``tests/test_tpu_compile.py`` compiles every main-path
kernel at its default blocking for a described v5e.
"""
from __future__ import annotations

import dataclasses

__all__ = [
    "KernelKnobs",
    "DEFAULT_KNOBS",
    "VMEM_LIMIT_BYTES",
    "vmem_bytes",
    "validate_real_kernel_knobs",
]

# Per-core VMEM on current TPU generations is 16 MiB; leave the usual
# ~25% to the compiler for scratch/semaphores and validate against 12.
VMEM_LIMIT_BYTES = 12 * 1024 * 1024

LANES = 128


@dataclasses.dataclass(frozen=True)
class KernelKnobs:
    """One kernel family's compiled-path blocking statics."""

    kernel: str
    block_n: int = 0       # data rows per grid step (IRLS kernels)
    block_rows: int = 0    # (rows, 128) tile rows (protocol kernels)

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


# The defaults the kernels ship with: fused_irls.DEFAULT_BLOCK_N and the
# min(256, rows) flat blocking in kernels/ops.py._flat_blocking.
DEFAULT_KNOBS = {
    "fused_irls": KernelKnobs("fused_irls", block_n=512),
    "fused_irls_cv": KernelKnobs("fused_irls_cv", block_n=512),
    "shamir_protect_flat": KernelKnobs("shamir_protect_flat",
                                       block_rows=256),
    "shamir_reveal_flat": KernelKnobs("shamir_reveal_flat", block_rows=256),
}


def vmem_bytes(knobs: KernelKnobs, *, d: int = 128, num_configs: int = 1,
               num_residues: int = 2, threshold: int = 2,
               num_points: int = 2, payload_bytes: int = 8) -> int:
    """Per-grid-step working set, in bytes, from static shapes alone.

    * ``fused_irls``: one (block_n, d) payload tile + its float32 mirror
      + y/count rows, beta in, and the (d, d) + (d,) + scalar
      accumulators (float32).
    * ``fused_irls_cv``: the same tile shared across ``num_configs``
      betas/accumulators, plus the fold-id row.
    * ``shamir_protect_flat``: (R, block_rows, 128) uint32 residues
      (the float payload is encoded in XLA, outside the kernel) +
      (R, t-1, block_rows, 128) uint32 coefficients +
      (R, P, block_rows, 128) uint32 share output.
    * ``shamir_reveal_flat``: (P, R, block_rows, 128) uint32 shares +
      (block_rows, 128) float64 output.
    """
    k = knobs.kernel
    if k in ("fused_irls", "fused_irls_cv"):
        bn = knobs.block_n
        tile = bn * d * (payload_bytes + 4) + bn * (4 + 4)  # X, Xm, y, cnt
        per_cfg = d * d * 4 + 2 * d * 4 + 8  # H + g/beta + dev
        return tile + num_configs * per_cfg
    if k == "shamir_protect_flat":
        br = knobs.block_rows
        payload = num_residues * br * LANES * 4
        coeffs = num_residues * (threshold - 1) * br * LANES * 4
        out = num_residues * num_points * br * LANES * 4
        return payload + coeffs + out
    if k == "shamir_reveal_flat":
        br = knobs.block_rows
        shares = num_points * num_residues * br * LANES * 4
        return shares + br * LANES * 8
    raise ValueError(f"unknown kernel family {k!r}")


def validate_real_kernel_knobs(knobs=None, *, d: int = 128,
                               num_configs: int = 1, num_residues: int = 2,
                               threshold: int = 2, num_points: int = 2,
                               vmem_limit_bytes: int = VMEM_LIMIT_BYTES):
    """Check every knob record against alignment + VMEM, pre-compilation.

    Returns one report dict per kernel (``{kernel, knob, vmem_bytes,
    vmem_limit_bytes, ok}``); raises ``ValueError`` on the first knob
    that could not compile at ``interpret=False`` — misaligned blocks or
    a working set past the limit.  Pure arithmetic: safe (and meaningful
    as documentation) on the CPU CI mesh where the interpreter would
    ignore the knobs entirely.
    """
    knobs = dict(DEFAULT_KNOBS if knobs is None else knobs)
    reports = []
    for name, kn in knobs.items():
        if kn.block_n:
            if kn.block_n % 8:
                raise ValueError(
                    f"{name}: block_n={kn.block_n} breaks the (8, 128) "
                    "float32 sublane tile"
                )
            if d % LANES:
                raise ValueError(
                    f"{name}: d={d} must be lane-aligned (multiple of "
                    f"{LANES}) for the compiled path — ops.py pads"
                )
        if kn.block_rows and kn.block_rows % 8:
            raise ValueError(
                f"{name}: block_rows={kn.block_rows} breaks the (8, 128) "
                "sublane tile"
            )
        need = vmem_bytes(
            kn, d=d, num_configs=num_configs, num_residues=num_residues,
            threshold=threshold, num_points=num_points,
        )
        ok = need <= vmem_limit_bytes
        if not ok:
            raise ValueError(
                f"{name}: working set {need} bytes exceeds VMEM budget "
                f"{vmem_limit_bytes} — shrink block_n/block_rows "
                f"({kn})"
            )
        reports.append({
            "kernel": name,
            "block_n": kn.block_n,
            "block_rows": kn.block_rows,
            "vmem_bytes": need,
            "vmem_limit_bytes": vmem_limit_bytes,
            "ok": ok,
        })
    return reports
