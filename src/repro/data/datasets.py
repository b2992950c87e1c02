"""Evaluation datasets shaped like the paper's four studies.

The paper evaluates on Insurance (COIL 2000; 9,822 x 84, 5 institutions),
Parkinsons.Motor / Parkinsons.Total (5,875 x 20, 5 institutions) and a
1M x 6 Synthetic study (6 institutions).  The real UCI files are not
available in this offline container, so we generate *deterministic
stand-ins with identical shapes and institution splits* — logistic
responses over correlated Gaussian covariates, binarized UPDRS-style
targets for the Parkinsons pair (same covariates, different responses,
matching the paper's sub-study construction).  All benchmark claims keyed
to these datasets (iterations-to-converge, central-vs-total runtime
shares, bytes transmitted) are structural and carry over; coefficient
values obviously differ from the real data and are never compared to the
paper's.
"""
from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp

from .partition import partition_rows
from .synthetic import generate_synthetic

__all__ = ["Study", "load_study", "STUDIES"]


@dataclasses.dataclass
class Study:
    name: str
    parts: list  # [(X_j, y_j)] per institution
    lam: float = 1.0

    @property
    def num_samples(self) -> int:
        return sum(int(p[0].shape[0]) for p in self.parts)

    @property
    def num_features(self) -> int:
        return int(self.parts[0][0].shape[1])

    def pooled(self):
        X = jnp.concatenate([p[0] for p in self.parts], axis=0)
        y = jnp.concatenate([p[1] for p in self.parts], axis=0)
        return X, y


def _logistic_table(key, n, d, num_inst, rho=0.3, dtype=jnp.float64):
    """Correlated-covariate logistic data, horizontally partitioned."""
    kb, kz, ke, ky = jax.random.split(key, 4)
    beta = jax.random.uniform(kb, (d,), minval=-0.8, maxval=0.8, dtype=dtype)
    common = jax.random.normal(kz, (n, 1), dtype=dtype)
    eps = jax.random.normal(ke, (n, d - 1), dtype=dtype)
    cov = jnp.sqrt(rho) * common + jnp.sqrt(1 - rho) * eps
    X = jnp.concatenate([jnp.ones((n, 1), dtype=dtype), cov], axis=1)
    y = jax.random.bernoulli(ky, jax.nn.sigmoid(X @ beta)).astype(dtype)
    return partition_rows(X, y, num_inst)


def load_study(name: str, seed: int = 0, scale: float = 1.0) -> Study:
    """``scale`` shrinks row counts for CI-speed runs (1.0 = paper size)."""
    # a stable digest of the name: hash() is salted per process, so it
    # would draw different rows in every run
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % (2**31) + seed)
    def rows(n):
        return max(64, int(n * scale))

    if name == "insurance":
        parts = _logistic_table(key, rows(9_822), 84, 5)
        return Study("insurance", parts, lam=1.0)
    if name in ("parkinsons.motor", "parkinsons.total"):
        # same covariates, different response (paper's two sub-studies)
        base = jax.random.PRNGKey(424242 + seed)
        kb, ky1, ky2 = jax.random.split(base, 3)
        n, d = rows(5_875), 20
        parts_x = _logistic_table(kb, n, d, 5)
        X = jnp.concatenate([p[0] for p in parts_x], axis=0)
        kk = ky1 if name.endswith("motor") else ky2
        kbeta, kber = jax.random.split(kk)
        beta = jax.random.uniform(kbeta, (d,), minval=-0.6, maxval=0.6,
                                  dtype=jnp.float64)
        y = jax.random.bernoulli(kber, jax.nn.sigmoid(X @ beta))
        return Study(name, partition_rows(X, y.astype(jnp.float64), 5), lam=1.0)
    if name == "synthetic":
        study = generate_synthetic(
            key,
            num_institutions=6,
            records_per_institution=rows(1_000_000 // 6),
            dim=6,
        )
        return Study("synthetic", list(study.parts), lam=1.0)
    raise KeyError(f"unknown study {name!r}")


STUDIES = ("insurance", "parkinsons.motor", "parkinsons.total", "synthetic")
