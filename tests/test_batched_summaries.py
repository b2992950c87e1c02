"""Batched ragged-institution summaries + the fused secure Newton path.

Pins the tentpole contracts: (a) one batched launch over padded ragged
partitions reproduces the per-institution ``local_summaries`` oracle
exactly (g/dev) / to f32-Gram tolerance (H); (b) the jit-resident fused
``secure_fit`` matches ``centralized_fit`` (paper Fig. 2, R^2 = 1) and the
pre-fusion loop path bit-for-bit up to fixed-point quantization, across
protect modes, backends, and uneven partitions including an institution
smaller than one kernel block; (c) the streaming aggregation path equals
the stacked-reduction oracle it replaced.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SecureAggregator,
    batched_local_summaries,
    centralized_fit,
    local_summaries,
    pack_cache_clear,
    pack_cache_evict,
    pack_cache_len,
    pack_partitions,
    secure_fit,
)
from repro.core import batched_summaries as bs_mod
from repro.core.field import fsum
from repro.data import generate_synthetic


@pytest.fixture(scope="module")
def study():
    return generate_synthetic(
        jax.random.PRNGKey(7), num_institutions=4,
        records_per_institution=300, dim=10,
    )


def _uneven_parts(study, sizes=(3, 170, 512, 515)):
    """Re-split the pooled study into deliberately ragged partitions.

    3 rows < any kernel block; the rest straddle block boundaries.
    """
    X, y = study.pooled()
    assert sum(sizes) == X.shape[0]
    parts, off = [], 0
    for s in sizes:
        parts.append((X[off:off + s], y[off:off + s]))
        off += s
    return parts


# ------------------------------------------------- batched summaries oracle
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_batched_matches_per_institution_oracle(study, backend):
    parts = _uneven_parts(study)
    packed = pack_partitions(parts)
    beta = 0.1 * jnp.arange(10, dtype=jnp.float64)
    out = batched_local_summaries(beta, packed, backend=backend)
    for j, (Xj, yj) in enumerate(parts):
        want = local_summaries(beta, Xj, yj)
        np.testing.assert_allclose(out.gradient[j], want.gradient,
                                   rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(out.deviance[j], want.deviance,
                                   rtol=1e-12)
        tol = dict(rtol=1e-9) if backend == "reference" else \
            dict(rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(out.hessian[j], want.hessian, **tol)
        assert int(out.count[j]) == Xj.shape[0]


def test_pack_partitions_memoized_per_study(study):
    """Same part arrays -> same packed object; new arrays -> fresh pack."""
    parts = _uneven_parts(study)
    p1 = pack_partitions(parts)
    p2 = pack_partitions(parts)
    assert p1 is p2
    assert pack_partitions(parts, dtype=jnp.float32) is not p1
    fresh = [(Xj + 0.0, yj) for Xj, yj in parts]  # new buffers, same values
    p3 = pack_partitions(fresh)
    assert p3 is not p1
    np.testing.assert_array_equal(np.asarray(p3.X), np.asarray(p1.X))


def test_pack_cache_serves_alternating_studies(study):
    """The LRU holds several studies at once: alternating between two
    part sets (the single-slot memo's thrash case) hits both ways."""
    parts_a = _uneven_parts(study)
    parts_b = [(Xj + 0.0, yj + 0.0) for Xj, yj in parts_a]
    pa, pb = pack_partitions(parts_a), pack_partitions(parts_b)
    assert pack_partitions(parts_a) is pa  # not evicted by study b
    assert pack_partitions(parts_b) is pb
    assert pack_partitions(parts_a) is pa


def test_pack_cache_bounded_lru():
    pack_cache_clear()
    keep = []
    for k in range(bs_mod._PACK_CACHE_SIZE + 3):
        parts = [(jnp.full((4, 3), float(k)), jnp.ones(4))]
        keep.append(parts)  # hold buffers so entries die only by LRU
        pack_partitions(parts)
    assert pack_cache_len() == bs_mod._PACK_CACHE_SIZE
    # oldest evicted, newest resident
    newest = pack_partitions(keep[-1])
    assert pack_partitions(keep[-1]) is newest


def test_pack_cache_entry_dies_with_its_buffers():
    """Evict-on-collect: when a part buffer is garbage collected the
    entry goes too, so a recycled id can never alias a stale pack."""
    import gc

    pack_cache_clear()
    parts = [(jnp.ones((4, 3)), jnp.ones(4))]
    pack_partitions(parts)
    assert pack_cache_len() == 1
    del parts
    gc.collect()
    assert pack_cache_len() == 0


def test_pack_cache_evict_on_churn(study):
    """pack_cache_evict drops every entry containing a churned buffer
    (the coordinator's add/remove_institution hook)."""
    pack_cache_clear()
    parts = _uneven_parts(study)
    p1 = pack_partitions(parts)
    assert pack_cache_len() == 1
    pack_cache_evict([parts[0]])
    assert pack_cache_len() == 0
    assert pack_partitions(parts) is not p1  # repacked, not resurrected


def test_pack_partitions_validates():
    X = jnp.ones((4, 3))
    with pytest.raises(ValueError, match="at least one"):
        pack_partitions([])
    with pytest.raises(ValueError, match="feature dimension"):
        pack_partitions([(X, jnp.ones(4)), (jnp.ones((2, 5)), jnp.ones(2))])
    packed = pack_partitions([(X, jnp.ones(4)), (2 * jnp.ones((1, 3)),
                                                 jnp.zeros(1))])
    assert packed.X.shape == (2, 4, 3)
    assert packed.total_records == 5
    assert packed.X32.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(packed.counts), [4, 1])
    # padding rows are zero (masking makes them inert either way)
    np.testing.assert_array_equal(np.asarray(packed.X[1, 1:]), 0.0)


@pytest.mark.parametrize("sizes,d,seed", [
    ((6, 52, 53), 4, 4113),  # the ragged shape XLA:CPU once mis-fused
    ((1, 300, 77, 300), 12, 0),
])
def test_pack_partitions_is_exact(sizes, d, seed):
    """Every packed row, the f32 operand and the zero padding equal a
    plain numpy pack of the same parts."""
    key = jax.random.PRNGKey(seed)
    parts = []
    for j, n in enumerate(sizes):
        kx, ky = jax.random.split(jax.random.fold_in(key, j))
        parts.append((
            jax.random.normal(kx, (n, d), dtype=jnp.float64),
            jax.random.bernoulli(ky, 0.5, (n,)).astype(jnp.float64),
        ))
    packed = pack_partitions(parts)
    want_X = np.zeros((len(sizes), max(sizes), d))
    want_y = np.zeros((len(sizes), max(sizes)))
    for s, (Xj, yj) in enumerate(parts):
        want_X[s, :Xj.shape[0]] = np.asarray(Xj)
        want_y[s, :yj.shape[0]] = np.asarray(yj)
    np.testing.assert_array_equal(np.asarray(packed.X), want_X)
    np.testing.assert_array_equal(np.asarray(packed.X32),
                                  want_X.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(packed.y), want_y)


# ----------------------------------------------------- secure_fit parity
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_secure_fit_uneven_partitions_match_gold(study, backend):
    """Fig. 2 on ragged partitions: R^2 = 1 vs the pooled gold standard,
    on both backends (reference -> loop path, pallas -> fused path)."""
    parts = _uneven_parts(study)
    gold = centralized_fit(*study.pooled(), lam=1.0)
    agg = SecureAggregator(backend=backend)
    sec = secure_fit(parts, lam=1.0, protect="both", aggregator=agg)
    assert sec.converged and gold.converged
    np.testing.assert_allclose(sec.beta, gold.beta, atol=1e-6)
    r2 = np.corrcoef(sec.beta, gold.beta)[0, 1] ** 2
    assert r2 > 0.999999


@pytest.mark.parametrize("protect", ["none", "gradient", "hessian", "both"])
def test_fused_matches_loop_within_quantization(study, protect):
    """The jit-resident fused iteration and the pre-fusion Python loop
    converge to the same beta well inside fixed-point quantization."""
    parts = _uneven_parts(study)
    agg = SecureAggregator(backend="pallas")
    loop = secure_fit(parts, protect=protect, aggregator=agg, fused=False)
    fus = secure_fit(parts, protect=protect, aggregator=agg, fused=True)
    quant = (len(parts) + 1) / agg.codec.scale
    assert fus.converged and loop.converged
    assert np.abs(fus.beta - loop.beta).max() <= quant
    assert fus.iterations == loop.iterations
    # telemetry comes from static shapes and must agree across paths
    assert fus.bytes_transmitted == loop.bytes_transmitted


def test_fused_requires_pallas_backend(study):
    with pytest.raises(ValueError, match="pallas"):
        secure_fit(study.parts, aggregator=SecureAggregator(), fused=True)


def test_fused_l1_prox_path(study):
    """Elastic-net solve goes through the same fused iteration."""
    parts = _uneven_parts(study)
    agg = SecureAggregator(backend="pallas")
    loop = secure_fit(parts, protect="gradient", aggregator=agg,
                      fused=False, l1=0.05)
    fus = secure_fit(parts, protect="gradient", aggregator=agg,
                     fused=True, l1=0.05)
    np.testing.assert_allclose(fus.beta, loop.beta, atol=1e-7)


# ------------------------------------------------- streaming aggregation
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_streaming_aggregate_equals_stacked_oracle(backend, rng_key):
    """The accumulator fold == the stacked single-reduction it replaced,
    element-exact in the field."""
    agg = SecureAggregator(backend=backend)
    tree = {"g": jnp.asarray([1.5, -2.25, 3.0]), "d": jnp.asarray(0.125)}
    prot = [agg.protect(jax.random.fold_in(rng_key, j), tree)
            for j in range(5)]
    got = agg.aggregate(prot)
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *prot
    )
    want = jax.tree_util.tree_map(
        lambda s: fsum(s, agg.scheme.field, axis=0, residue_axis=1), stacked
    )
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out = agg.reveal(got)
    np.testing.assert_allclose(np.asarray(out["g"]),
                               5 * np.asarray(tree["g"]), atol=1e-6)


def test_protect_batched_roundtrip(rng_key):
    """protect_batched + aggregate_batched == sum of the S inputs."""
    agg = SecureAggregator(backend="pallas")
    tree = {
        "h": jnp.arange(24, dtype=jnp.float64).reshape(3, 2, 4),
        "dev": jnp.asarray([0.5, -1.5, 2.0]),
    }
    prot = agg.protect_batched(rng_key, tree)
    assert prot.buf.shape[2] == 3  # S axis
    agg_b = agg.aggregate_batched(prot)
    out = agg.reveal(agg_b)
    np.testing.assert_allclose(
        np.asarray(out["h"]), np.asarray(jnp.sum(tree["h"], axis=0)),
        atol=3 * 0.5 / agg.codec.scale,
    )
    np.testing.assert_allclose(
        np.asarray(out["dev"]), float(jnp.sum(tree["dev"])),
        atol=3 * 0.5 / agg.codec.scale,
    )
    with pytest.raises(ValueError, match="pallas"):
        SecureAggregator().protect_batched(rng_key, tree)
