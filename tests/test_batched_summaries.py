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


# ------------------------------------------- float64 terms from bf16 slices
D128 = 128  # the acceptance width; the slices hold exact up to it


def _slice_case(case):
    """(X (S, N, d) packed float64, y, counts) for one accuracy case."""
    rng = np.random.default_rng(151)
    sizes = (1, 130, 300, 77)
    X = rng.standard_normal((len(sizes), max(sizes), D128))
    if case == "intercept":
        X[..., 0] = 1.0
    elif case == "zero_row":
        X[2, 40] = 0.0
    elif case == "scaled_rows":
        X[2, 5] *= 2.0 ** 20
        X[2, 6] *= 2.0 ** -20
        X[3, 70] *= 2.0 ** -20
    elif case == "standardized":
        X[..., 0] = 1.0
        X[..., 1:] = 3.0 + 40.0 * X[..., 1:]
        pooled = np.concatenate([X[s, :n] for s, n in enumerate(sizes)])
        X[..., 1:] = ((X[..., 1:] - pooled[:, 1:].mean(0))
                      / pooled[:, 1:].std(0))
    elif case == "unit_scale":
        X = rng.random(X.shape)
    counts = np.asarray(sizes, np.int32)
    y = (rng.random(X.shape[:2]) < 0.5).astype(np.float64)
    for s, n in enumerate(sizes):  # padding rows hold zeros, as packed
        X[s, n:] = 0.0
        y[s, n:] = 0.0
    return X, y, counts


@pytest.mark.parametrize("case", ["ragged", "zero_row", "intercept",
                                  "scaled_rows", "standardized",
                                  "unit_scale"])
def test_sliced_terms_hold_float64_bounds(case):
    """z, g and dev from the bf16 slices of X against numpy float64, each
    within a float64 dot's error bound: d 2**-53 sum|x||beta| per row for
    z, N 2**-53 sum_n |x_nk||r_n| per column for g; for dev, the N-term
    sum's bound plus what the error of z moves it by."""
    from repro.kernels.sliced_terms import cut_slices, sliced_terms

    X, y, counts = _slice_case(case)
    s_dim, n, d = X.shape
    beta = np.random.default_rng(7).uniform(-0.2, 0.2, d)
    beta[0] = 0.3
    _, g, dev = jax.jit(sliced_terms)(
        jnp.asarray(beta), cut_slices(jnp.asarray(X)), jnp.asarray(y),
        jnp.asarray(counts))
    mask = (np.arange(n)[None, :] < counts[:, None]).astype(np.float64)
    z = np.einsum("snd,d->sn", X, beta)
    p = 1.0 / (1.0 + np.exp(-z))
    r = (y - p) * mask
    want_g = np.einsum("snd,sn->sd", X, r)
    ll = (y * z - np.logaddexp(0.0, z)) * mask
    want_dev = -2.0 * ll.sum(axis=1)
    u = 2.0 ** -53
    xb = np.einsum("snd,d->sn", np.abs(X), np.abs(beta))
    bound_g = n * u * np.einsum("snd,sn->sd", np.abs(X), np.abs(r))
    bound_dev = 2.0 * u * (n * np.abs(ll).sum(axis=1)
                           + d * (xb * mask).sum(axis=1))
    assert np.all(np.abs(np.asarray(g) - want_g) <= bound_g)
    assert np.all(np.abs(np.asarray(dev) - want_dev) <= bound_dev)
    # z itself, from the same levels the terms combine
    from repro.kernels.sliced_terms import K, z_levels
    digits, scale = cut_slices(jnp.asarray(X))
    zl, bscale = z_levels(jnp.asarray(beta), digits)
    zfix = sum(np.asarray(zl[lv], np.float64) * 2.0 ** (-8 * (lv + 2))
               for lv in range(K))
    got_z = (zfix * np.asarray(scale) * float(bscale))[:, :n]
    assert np.all(np.abs(got_z - z) <= d * u * xb)


def test_sliced_partials_are_exact_integers():
    """Every digit is an integer of magnitude at most 128, X is its slices
    to within 2**-55 of its row's largest entry, and every f32 partial of
    the two dots is an integer under 2**24: the slice width holds."""
    from repro.kernels.sliced_terms import (
        K, KQ, SLAB, cut_slices, g_levels, z_levels)

    X, y, counts = _slice_case("scaled_rows")
    s_dim, n, d = X.shape
    digits, scale = cut_slices(jnp.asarray(X))
    a = np.asarray(digits, np.float64)
    assert a.shape == (s_dim, -(-n // SLAB) * SLAB, K * d)
    assert np.all(a == np.round(a)) and np.abs(a).max() <= 128
    back = sum(a[..., i * d:(i + 1) * d] * 2.0 ** (-8 * (i + 1))
               for i in range(K)) * np.asarray(scale)[..., None]
    row_max = np.abs(X).max(axis=2, keepdims=True)
    assert np.all(np.abs(back[:, :n] - X) <= 2.0 ** -55 * row_max)
    beta = jnp.asarray(np.random.default_rng(3).uniform(-1.0, 1.0, d))
    zl, _ = z_levels(beta, digits)
    q = np.random.default_rng(4).uniform(-1.0, 1.0, a.shape[:2])
    q = q * np.asarray(scale)
    gl, _ = g_levels(jnp.asarray(q), digits)
    assert zl.dtype == gl.dtype == jnp.float32
    assert gl.shape == (s_dim, a.shape[1] // SLAB, KQ, d)
    for part in (np.asarray(zl, np.float64), np.asarray(gl, np.float64)):
        assert np.all(part == np.round(part))
        assert np.abs(part).max() < 2.0 ** 24


def test_secure_fit_through_sliced_terms_matches_float64_terms(study):
    """A whole secure fit whose gradient and deviance come from the slices
    runs the same rounds as the fit on ``_sim_terms`` (float64
    contractions over X) and lands on the same beta within 1e-12."""
    from repro.core import SecureCollective
    from repro.core.scanfit import fit_scan_block
    from repro.kernels.sliced_terms import cut_slices

    packed = pack_partitions(_uneven_parts(study))
    agg = SecureCollective(backend="pallas")
    dim = packed.dim

    def fit(slices):
        carry, _, actives, _, _ = fit_scan_block(
            jnp.zeros(dim, jnp.float64), jnp.asarray(np.inf),
            jnp.asarray(False), jnp.zeros((), jnp.int32),
            jax.random.PRNGKey(0), jnp.zeros((), jnp.int32),
            packed.X, packed.X32, slices, packed.y, packed.counts,
            jnp.asarray(1.0), agg=agg, protect="both", l1=0.0, tol=1e-10,
            points=None, include_count=True, summaries_backend="pallas",
            num_rounds=12, num_parts=packed.num_institutions,
            max_rounds=12)
        return np.asarray(carry[0]), int(carry[3]), bool(carry[2])

    beta_sim, rounds_sim, conv_sim = fit(None)
    beta_cut, rounds_cut, conv_cut = fit(cut_slices(packed.X))
    assert conv_sim and conv_cut
    assert rounds_cut == rounds_sim
    np.testing.assert_allclose(beta_cut, beta_sim, rtol=0, atol=1e-12)


def test_pack_cuts_slices_once_for_the_compiled_float64_rung(
        study, monkeypatch):
    """Only the compiled ``pallas`` rung with a float64 payload of at
    most 128 features gets slices: cut on its first pack, kept with the
    cached pack and counted once; the CPU's simulation, the other rungs,
    an f32 payload and a wider X get none."""
    import repro.kernels.backend as kernel_backend
    from repro.obs import metrics

    parts = _uneven_parts(study)
    pack_cache_clear()
    assert pack_partitions(parts, backend="pallas").slices is None
    before = metrics.get("repro_f64_slice_packs_total") or 0.0
    monkeypatch.setattr(kernel_backend, "interpret_kernels", lambda: False)
    for rung in ("reference", "mixed"):
        assert pack_partitions(parts, backend=rung).slices is None
    assert pack_partitions(parts, dtype=jnp.float32,
                           backend="pallas").slices is None
    wide = [(jnp.ones((3, 129)), jnp.ones(3))]
    assert pack_partitions(wide, backend="pallas").slices is None
    first = pack_partitions(parts, backend="pallas")
    assert first.slices is not None
    assert pack_partitions(parts, backend="pallas") is first
    assert pack_partitions(parts) is first  # the cache keeps the slices
    assert metrics.get("repro_f64_slice_packs_total") == before + 1.0
    pack_cache_clear()
