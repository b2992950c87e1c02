"""Seeded consortium data, made on the device in one jitted call.

A copy of the paper's Algorithm 3 (``repro.data.generate_synthetic``) and
of the institution splits the repository runs, kept here so that a change
to the program's data code cannot move the benchmark's inputs:

* ``beta ~ U(-beta_scale, beta_scale)`` over ``features`` columns (the
  first is the intercept);
* ``X = [1 | N(mu, sigma^2)]`` row by row, ``y ~ Bernoulli(sigmoid(X beta))``;
* ``split = "equal"``: every site holds ``rows // sites`` rows (the
  paper's Synthetic study, ``data/datasets.py``);
* ``split = "ramp5"``: a +-5% linear ramp of site sizes around the mean,
  the last site taking the remainder (``benchmarks/e2e_secure_fit.py``);
* ``split = "zipf"``: site ``k`` (from 1) holds rows in proportion to
  ``k ** -split_exponent``, rounded down, the largest taking the
  remainder.

``fold_ids`` copies the program's cross-validation fold assignment
(``repro.selection.folds.assign_folds``): a permutation of
``arange(rows) % folds`` keyed by the fold seed and the crc32 of the
site's name.

Rows are float64, the payload dtype the secure fit is run with.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["site_sizes", "make_parts", "seed_key", "fold_ids"]


def seed_key(seed: int, stream: int) -> jax.Array:
    """A JAX key for one named stream of a run, from a seed of any size."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def site_sizes(rows: int, sites: int, split: str,
               exponent: float = 0.0) -> list[int]:
    base = rows // sites
    if split == "equal":
        return [base] * sites
    if split == "ramp5":
        sizes = [base + int(base * 0.05 * (2 * j / max(sites - 1, 1) - 1))
                 for j in range(sites)]
        sizes[-1] += rows - sum(sizes)
        return sizes
    if split == "zipf":
        w = np.arange(1, sites + 1, dtype=np.float64) ** -exponent
        sizes = [int(v) for v in np.floor(rows * w / w.sum())]
        sizes[0] += rows - sum(sizes)
        return sizes
    raise ValueError(f"unknown split {split!r}")


@functools.partial(jax.jit, static_argnames=("sizes", "features", "mu",
                                             "sigma", "beta_scale"))
def _generate(key, sizes, features, mu, sigma, beta_scale):
    k_beta, k_cov, k_y = jax.random.split(key, 3)
    n = sum(sizes)
    beta = jax.random.uniform(k_beta, (features,), jnp.float64,
                              -beta_scale, beta_scale)
    cov = mu + sigma * jax.random.normal(k_cov, (n, features - 1),
                                         jnp.float64)
    X = jnp.concatenate([jnp.ones((n, 1), jnp.float64), cov], axis=1)
    y = jax.random.bernoulli(k_y, jax.nn.sigmoid(X @ beta)).astype(
        jnp.float64)
    parts, off = [], 0
    for size in sizes:
        parts.append((X[off:off + size], y[off:off + size]))
        off += size
    return tuple(parts)


def make_parts(config: dict, key: jax.Array) -> list[tuple]:
    """The configuration's sites as ``[(X_j, y_j)]`` device arrays."""
    gen = config["generator"]
    sizes = tuple(site_sizes(config["rows"], config["sites"],
                             config["split"],
                             config.get("split_exponent", 0.0)))
    return list(_generate(key, sizes, config["features"], float(gen["mu"]),
                          float(gen["sigma"]), float(gen["beta_scale"])))


def fold_ids(rows: int, folds: int, name: str, fold_seed: int) -> np.ndarray:
    """(rows,) int32 fold of each of a site's rows, as the program assigns
    them from the site's ``name`` and the job's ``fold_seed``."""
    key = jax.random.fold_in(jax.random.PRNGKey(fold_seed),
                             zlib.crc32(str(name).encode()) & 0x7FFFFFFF)
    pattern = jnp.arange(rows, dtype=jnp.int32) % folds
    return np.asarray(jax.random.permutation(key, pattern))
