"""The plain pooled fit that decides ``correct``: no secret sharing, no
kernels, no batching across sites.

L2-regularized logistic regression by Newton's method from beta = 0,
over all sites' rows pooled into one data set:

    objective(beta) = -2 log L(beta) + lam ||beta||^2
    beta <- beta + (X^T W X + lam I)^{-1} (X^T (y - p) - lam beta)

It stops when two successive objectives differ by less than
``tol * (1 + |objective|)`` and keeps the beta that objective was taken
at, the rule the paper states for Algorithm 1.  It imports nothing of the
system under test.

A ``Precision`` says how it computes: float64 throughout, at
``highest``, is the reference.  The controls compute the same steps
lower, which a sound comparison has to refuse: ``lower`` takes every
term one rung down, ``gram_lower`` only the Gram (the Hessian).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Precision", "lower", "gram_lower", "CONTROLS", "Trajectory",
           "fit", "objective", "deviance", "pool"]

@dataclasses.dataclass(frozen=True)
class Precision:
    """The dtype of the row terms (z, p, gradient, deviance) and of the
    Gram, and the matmul precision of the Gram."""

    terms: str = "float64"
    gram: str = "float64"
    gram_precision: str = "highest"


def lower(stated: dict) -> Precision:
    """The rung below what a configuration states: float64 -> float32 (at
    ``highest``), float32 at ``highest`` -> float32 at ``default`` (one
    bfloat16 pass on a TPU)."""
    terms = {"float64": "float32"}[stated["terms"]]
    if stated["gram"] == "float64":
        return Precision(terms, "float32", "highest")
    if stated["gram"] == "float32" and stated["gram_precision"] == "highest":
        return Precision(terms, "float32", "default")
    raise ValueError(f"no lower rung for {stated}")


def gram_lower(stated: dict) -> Precision:
    """The terms as stated, the Gram one rung lower: float64 -> float32
    at ``highest``, float32 at ``highest`` -> float32 at ``default``."""
    rung = lower(stated)
    return Precision(stated["terms"], rung.gram, rung.gram_precision)


CONTROLS = {"lower": lower, "gram": gram_lower}


@functools.partial(jax.jit, static_argnames=("terms", "gram", "prec"))
def _site_terms(beta, X, y, terms, gram, prec):
    hi = jax.lax.Precision.HIGHEST
    Xt = X.astype(terms)
    z = jnp.matmul(Xt, beta.astype(terms), precision=hi)
    p = jax.nn.sigmoid(z)
    g = jnp.matmul(Xt.T, y.astype(terms) - p, precision=hi)
    dev = -2.0 * jnp.sum(y.astype(terms) * z - jnp.logaddexp(0.0, z))
    Xg = X.astype(gram)
    w = (p * (1.0 - p)).astype(gram)
    if prec == "default":
        # one bfloat16 pass with float32 sums, as a TPU runs ``default``
        # and on every backend alike
        a = (Xg * w[:, None]).astype(jnp.bfloat16)
        H = jnp.matmul(a.T, Xg.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(gram)
    else:
        H = jnp.matmul((Xg * w[:, None]).T, Xg, precision=hi)
    return H, g, dev


@functools.partial(jax.jit, static_argnames=("dtype",))
def _site_deviance(beta, X, y, dtype):
    X, y = X.astype(dtype), y.astype(dtype)
    z = jnp.matmul(X, beta.astype(dtype), precision=jax.lax.Precision.HIGHEST)
    return -2.0 * jnp.sum(y * z - jnp.logaddexp(0.0, z))


@functools.partial(jax.jit, static_argnames=("dtype",))
def _newton(beta, H, g, lam, dtype):
    A = H.astype(dtype) + lam * jnp.eye(H.shape[0], dtype=dtype)
    rhs = g.astype(dtype) - lam * beta
    return beta + jax.scipy.linalg.cho_solve(
        jax.scipy.linalg.cho_factor(A), rhs)


def pool(parts):
    """All sites' rows as one ``(X, y)``, on the sites' device."""
    if len(parts) == 1:
        return parts[0]
    return (jnp.concatenate([X for X, _ in parts]),
            jnp.concatenate([y for _, y in parts]))


def deviance(beta, parts, dtype: str = "float64") -> float:
    """-2 log L at ``beta`` over ``parts``' rows, in ``dtype``."""
    X, y = pool(parts)
    return float(_site_deviance(jnp.asarray(beta), X, y, dtype))


def objective(beta, parts, lam: float) -> float:
    """-2 log L + lam ||beta||^2 at ``beta``, in float64."""
    beta = np.asarray(beta, np.float64)
    return deviance(beta, parts) + lam * float(np.sum(beta * beta))


@dataclasses.dataclass
class Trajectory:
    """Every Newton iterate from beta = 0 and the objective at each."""

    betas: list  # betas[k]: after k Newton steps, float64 numpy
    objectives: list  # objectives[k] at betas[k]
    rounds: int  # objectives taken up to the stop (the stopping one too)
    converged: bool

    @property
    def beta(self) -> np.ndarray:
        """The beta the fit returns: the one its last objective was at."""
        return self.betas[self.rounds - 1]


def fit(parts, lam: float, prec: Precision = Precision(), tol: float = 1e-10,
        max_rounds: int = 50, min_steps: int = 0,
        past: int = 0) -> Trajectory:
    """Newton from zero until the stopping rule holds; then on, without
    stopping, until ``min_steps`` steps in all and ``past`` steps after
    the stop have been taken."""
    X, y = pool(parts)
    beta = jnp.zeros((X.shape[1],), prec.terms)
    betas, objs = [], []
    obj_prev, rounds, converged = np.inf, 0, False
    k = 0
    while True:
        H, g, dev = _site_terms(beta, X, y, prec.terms, prec.gram,
                                prec.gram_precision)
        obj = float(dev + lam * jnp.sum(beta * beta))
        betas.append(np.asarray(beta, np.float64))
        objs.append(obj)
        if not converged and rounds < max_rounds:
            rounds += 1
            if abs(obj_prev - obj) < tol * (1.0 + abs(obj)):
                converged = True
            obj_prev = obj
        if ((converged or rounds >= max_rounds) and k >= min_steps
                and k >= rounds - 1 + past):
            break
        beta = _newton(beta, H, g, lam, prec.terms)
        k += 1
    return Trajectory(betas, objs, rounds, converged)
