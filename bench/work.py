"""Operations and bytes that the secure fit needs, worked out from shapes.

Every count is of useful work on the unpadded shapes: ``rows`` valid
rows over ``sites`` sites and ``features`` columns.  Padding, repeated
reads and work the program throws away are not counted, so a share of a
peak computed from these can only read low, never high.

* ``fit_round_flops``: one secure Newton round of one fit: the summaries
  (Gram ``2 N d^2``, the linear predictor and the gradient ``4 N d``)
  and the solve (Cholesky ``d^3 / 3``).
* ``irls_kernel``: the fused summaries kernel per round: the same
  summaries operations; bytes of its own inputs read once (the float32
  rows and labels) and outputs written once (per-site Gram, gradient,
  deviance), all float32 as the kernel takes them.
* ``share_kernel`` / ``reconstruct_kernel``: the Shamir protect and
  reveal kernels per round, bytes only (the VPU's integer rate is not
  published).  Protect reads each site's residues and ``t - 1``
  polynomial coefficients and writes ``w`` shares of each; reveal reads
  the ``t`` shares that reconstruction needs and writes the residues.
  The protected tree with ``protect = both`` holds, per site, the
  Hessian, the gradient, the deviance and the row count; on the
  selection path each of ``configs`` (lambda x fold) configurations
  ships its own, with three held-out scalars more (``extra``).
* ``irls_cv_kernel``: the cross-validated summaries kernel per round
  over ``configs`` configurations: each one's Gram and gradient over its
  training rows and its linear predictor over all rows; bytes of
  the float32 rows, labels and fold ids read once, each configuration's
  per-site Gram, gradient and four scalars written once.
"""
from __future__ import annotations

__all__ = ["fit_round_flops", "irls_kernel", "irls_cv_kernel",
           "share_kernel", "reconstruct_kernel", "protected_elements"]

F32 = 4
U32 = 4


def fit_round_flops(rows: int, features: int) -> float:
    n, d = rows, features
    return 2.0 * n * d * d + 4.0 * n * d + d ** 3 / 3.0


def irls_kernel(rows: int, sites: int, features: int) -> tuple[float, float]:
    """(operations, bytes) of one launch over all sites."""
    n, s, d = rows, sites, features
    flops = 2.0 * n * d * d + 4.0 * n * d
    read = F32 * (n * d + n + d)
    written = F32 * (s * d * d + s * d + s)
    return flops, float(read + written)


def irls_cv_kernel(rows: int, sites: int, features: int, configs: int,
                   held_out: int) -> tuple[float, float]:
    """(operations, bytes) of one launch over all sites and ``configs``
    configurations, of which ``held_out`` rows in all are held out."""
    n, s, d, c = rows, sites, features, configs
    trained = c * n - held_out
    flops = 2.0 * trained * d * d + 2.0 * c * n * d + 2.0 * trained * d
    read = F32 * (n * d + 2 * n + c * d)
    written = F32 * c * s * (d * d + d + 4)
    return flops, float(read + written)


def protected_elements(features: int, protect: str, extra: int = 0) -> int:
    """Field elements one site protects per round (count leaf included)."""
    d = features
    per = {"both": d * d + d, "hessian": d * d, "gradient": d}[protect]
    return per + 2 + extra


def share_kernel(sites: int, features: int, protect: str, residues: int,
                 threshold: int, centers: int, configs: int = 1,
                 extra: int = 0) -> float:
    """Bytes of one protect launch over all sites and configurations."""
    e = configs * sites * protected_elements(features, protect, extra) \
        * residues
    return float(U32 * e * (1 + (threshold - 1) + centers))


def reconstruct_kernel(features: int, protect: str, residues: int,
                       threshold: int, configs: int = 1,
                       extra: int = 0) -> float:
    """Bytes of one reveal launch of the configurations' aggregates."""
    e = configs * protected_elements(features, protect, extra) * residues
    return float(U32 * e * (threshold + 1))
