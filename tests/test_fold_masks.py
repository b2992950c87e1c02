"""Property tests: fold masks composed onto the packed ragged row masks.

The selection subsystem's correctness keystone — for arbitrary uneven
partitions and fold assignments, the fold∘row-masked summaries over the
padded (S, N_max, d) batch must reproduce what ``local_summaries`` says
about the physically-sliced per-fold partitions, on both rungs of the
summaries ladder ("reference" f64 exact; "pallas" f32-Gram to operand
tolerance), and the held-out metrics must mirror plain evaluation of the
held-out slices exactly (deviance to float roundoff; correct/count as
exact integers).

Runs under real hypothesis when installed, else the deterministic
fallback shim (tests/_hypothesis_fallback.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    batched_cv_summaries,
    batched_local_summaries,
    local_summaries,
    pack_partitions,
)
from repro.core.logreg import deviance as deviance_fn
from repro.selection import assign_folds, pack_fold_ids


def _random_study(rng_seed, sizes, d):
    key = jax.random.PRNGKey(rng_seed)
    parts = []
    for j, n in enumerate(sizes):
        k1, k2 = jax.random.split(jax.random.fold_in(key, j))
        Xj = jax.random.normal(k1, (n, d), dtype=jnp.float64)
        yj = jax.random.bernoulli(k2, 0.55, (n,)).astype(jnp.float64)
        parts.append((Xj, yj))
    return parts


def _cv_setup(sizes, d, num_folds, fold_seed):
    parts = _random_study(fold_seed + 17, sizes, d)
    folds = [
        assign_folds(n, num_folds, f"inst{j}", fold_seed)
        for j, n in enumerate(sizes)
    ]
    packed = pack_partitions(parts)
    fold_ids = pack_fold_ids(folds, packed.X.shape[1])
    return parts, folds, packed, fold_ids


def _check_fold_masks_vs_local_summaries(backend, sizes, num_folds,
                                         fold_seed, d=5):
    """Shared property body: fold∘row masks over the packed batch ==
    local_summaries on the unpacked per-fold partitions."""
    sizes = [max(s, num_folds) for s in sizes]
    parts, folds, packed, fold_ids = _cv_setup(
        sizes, d, num_folds, fold_seed
    )
    betas = jnp.stack([
        0.07 * (c + 1) * jnp.arange(d, dtype=jnp.float64) - 0.1
        for c in range(num_folds)
    ])
    fold_of = jnp.arange(num_folds, dtype=jnp.int32)
    sm = batched_cv_summaries(
        betas, packed, fold_ids, fold_of, backend=backend
    )
    h_tol = dict(rtol=1e-9, atol=1e-9) if backend == "reference" else \
        dict(rtol=2e-4, atol=2e-4)
    for c in range(num_folds):
        for s, ((Xj, yj), f) in enumerate(zip(parts, folds)):
            tr = np.asarray(f) != c
            want = local_summaries(betas[c], Xj[tr], yj[tr])
            np.testing.assert_allclose(
                sm.gradient[c, s], want.gradient, rtol=1e-9, atol=1e-9
            )
            np.testing.assert_allclose(
                sm.deviance[c, s], want.deviance, rtol=1e-12, atol=1e-9
            )
            np.testing.assert_allclose(
                sm.hessian[c, s], want.hessian, **h_tol
            )
            assert int(sm.count[c, s]) == int(tr.sum())


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@settings(max_examples=3, deadline=None)
@given(
    sizes=st.lists(st.integers(4, 90), min_size=2, max_size=5),
    num_folds=st.integers(2, 4),
    fold_seed=st.integers(0, 2**16),
)
def test_fold_masks_reproduce_per_fold_local_summaries(
    backend, sizes, num_folds, fold_seed
):
    """Both summaries_backend rungs, a few drawn shapes (tier-1 size;
    the exhaustive sweep is the `slow`-marked variant below)."""
    _check_fold_masks_vs_local_summaries(backend, sizes, num_folds,
                                         fold_seed)


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["reference", "pallas", "mixed"])
@settings(max_examples=20, deadline=None)
@given(
    sizes=st.lists(st.integers(4, 300), min_size=2, max_size=7),
    num_folds=st.integers(2, 6),
    fold_seed=st.integers(0, 2**20),
)
def test_fold_masks_property_exhaustive(backend, sizes, num_folds,
                                        fold_seed):
    """The wide sweep (all three rungs, larger/raggeder partitions);
    excluded from tier-1 by the `slow` marker — run with -m slow."""
    _check_fold_masks_vs_local_summaries(backend, sizes, num_folds,
                                         fold_seed, d=6)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@settings(max_examples=3, deadline=None)
@given(
    sizes=st.lists(st.integers(6, 80), min_size=2, max_size=4),
    num_folds=st.integers(2, 5),
    fold_seed=st.integers(0, 2**16),
)
def test_heldout_metrics_match_plain_evaluation(
    backend, sizes, num_folds, fold_seed
):
    """val deviance == plain deviance of the held-out slice; correct and
    count are exact integers matching plain thresholded predictions."""
    sizes = [max(s, num_folds) for s in sizes]
    d = 4
    parts, folds, packed, fold_ids = _cv_setup(
        sizes, d, num_folds, fold_seed
    )
    beta = 0.3 - 0.05 * jnp.arange(d, dtype=jnp.float64)
    fold_of = jnp.arange(num_folds, dtype=jnp.int32)
    sm = batched_cv_summaries(
        jnp.tile(beta[None], (num_folds, 1)), packed, fold_ids, fold_of,
        backend=backend,
    )
    for c in range(num_folds):
        for s, ((Xj, yj), f) in enumerate(zip(parts, folds)):
            va = np.asarray(f) == c
            assert int(sm.val_count[c, s]) == int(va.sum())
            if not va.any():
                assert float(sm.val_deviance[c, s]) == 0.0
                assert float(sm.val_correct[c, s]) == 0.0
                continue
            np.testing.assert_allclose(
                sm.val_deviance[c, s],
                deviance_fn(beta, Xj[va], yj[va]),
                rtol=1e-12, atol=1e-9,
            )
            z = np.asarray(Xj[va] @ beta)
            correct = int(((z > 0) == (np.asarray(yj[va]) > 0.5)).sum())
            assert int(sm.val_correct[c, s]) == correct


@pytest.mark.parametrize("backend", ["reference", "pallas", "mixed"])
def test_train_plus_heldout_partitions_the_full_summaries(backend):
    """Row partition invariant: train deviance + held-out deviance ==
    full deviance, and a fold_of == -1 config == the non-CV batched
    summaries (full-data fit sharing the launch)."""
    sizes = (23, 57, 11)
    d, K = 6, 3
    parts, folds, packed, fold_ids = _cv_setup(list(sizes), d, K, 9)
    beta = 0.11 * jnp.arange(d, dtype=jnp.float64)
    betas = jnp.tile(beta[None], (K + 1, 1))
    fold_of = jnp.asarray(list(range(K)) + [-1], jnp.int32)
    sm = batched_cv_summaries(betas, packed, fold_ids, fold_of,
                              backend=backend)
    full = batched_local_summaries(
        beta, packed, backend="reference"
    )
    for c in range(K):
        np.testing.assert_allclose(
            np.asarray(sm.deviance[c]) + np.asarray(sm.val_deviance[c]),
            np.asarray(full.deviance), rtol=1e-12,
        )
        np.testing.assert_array_equal(
            np.asarray(sm.count[c]) + np.asarray(sm.val_count[c]),
            np.asarray(packed.counts).astype(np.float64),
        )
    # the full-data config: empty held-out masks, train == everything
    np.testing.assert_allclose(sm.deviance[K], full.deviance, rtol=1e-12)
    np.testing.assert_allclose(sm.gradient[K], full.gradient,
                               rtol=1e-9, atol=1e-9)
    assert float(np.asarray(sm.val_count[K]).sum()) == 0.0
    h_tol = dict(rtol=1e-9) if backend == "reference" else \
        dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(sm.hessian[K], full.hessian, **h_tol)


def test_cv_kernel_matches_simulation():
    """The blocked Pallas CV kernel (interpreted) == the XLA functional
    simulation, with an f64 payload where their accumulation contracts
    coincide — the same pinning the non-CV fused_irls kernel has."""
    from repro.kernels import ops

    sizes = (3, 170, 64)
    d, K = 5, 3
    parts, folds, packed, fold_ids = _cv_setup(list(sizes), d, K, 4)
    betas = jnp.stack([
        0.05 * (c + 1) * jnp.arange(d, dtype=jnp.float64)
        for c in range(K + 1)
    ])
    fold_of = jnp.asarray(list(range(K)) + [-1], jnp.int32)
    kw = dict(counts=packed.counts, mxu_operand=packed.X32)
    out_kernel = ops.fused_irls_cv(
        betas, packed.X, packed.y, fold_ids, fold_of, simulate=False, **kw
    )
    out_sim = ops.fused_irls_cv(
        betas, packed.X, packed.y, fold_ids, fold_of, simulate=True, **kw
    )
    names = ("hessian", "gradient", "dev_train", "dev_val", "correct",
             "count_val")
    for a, b, name in zip(out_kernel, out_sim, names):
        np.testing.assert_allclose(
            np.asarray(a, np.float64), np.asarray(b, np.float64),
            rtol=1e-6 if name == "hessian" else 1e-11,
            atol=1e-6 if name == "hessian" else 1e-11,
            err_msg=name,
        )


def test_churn_safe_fold_assignment():
    """Folds are a pure function of (name, seed): stable across cohort
    composition, balanced within an institution, deterministic."""
    a = np.asarray(assign_folds(103, 5, "hospital-a", fold_seed=3))
    b = np.asarray(assign_folds(103, 5, "hospital-a", fold_seed=3))
    np.testing.assert_array_equal(a, b)
    # balanced: sizes differ by at most one
    counts = np.bincount(a, minlength=5)
    assert counts.max() - counts.min() <= 1
    # another institution draws a different permutation
    c = np.asarray(assign_folds(103, 5, "hospital-b", fold_seed=3))
    assert (a != c).any()
    # different seed reshuffles
    d = np.asarray(assign_folds(103, 5, "hospital-a", fold_seed=4))
    assert (a != d).any()
    with pytest.raises(ValueError, match="folds"):
        assign_folds(3, 5, "tiny")
    with pytest.raises(ValueError, match="at least 2"):
        assign_folds(10, 1, "x")
