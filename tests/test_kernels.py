"""Per-kernel allclose vs pure-jnp oracle, shape/dtype sweeps (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.shamir_poly import mulmod31, addmod

P31 = 2**31 - 1
P31B = 2**31 - 19


# ------------------------------------------------------------- backend switch
def test_backend_decides_interpret(monkeypatch):
    """Interpreted on the CPU, compiled on the TPU, refused elsewhere —
    no caller chooses."""
    from repro.kernels import backend

    assert backend.interpret_kernels() is True
    assert backend.resolve_interpret(False) is False
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
    assert backend.interpret_kernels() is False
    assert backend.resolve_interpret(None) is False
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        backend.interpret_kernels()


# ---------------------------------------------------------------- gram_hessian
@pytest.mark.parametrize("n", [8, 100, 512, 1000])
@pytest.mark.parametrize("d", [3, 84, 128, 200])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_gram_hessian_matches_ref(n, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n * 1000 + d))
    X = jax.random.normal(k1, (n, d), dtype=dtype)
    w = jax.random.uniform(k2, (n,), dtype=dtype, minval=0.0, maxval=0.25)
    got = ops.gram_hessian(X, w)
    want = ref.gram_hessian(X, w)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_gram_hessian_block_sweep():
    X = jax.random.normal(jax.random.PRNGKey(0), (777, 84))
    w = jax.random.uniform(jax.random.PRNGKey(1), (777,))
    want = ref.gram_hessian(X, w)
    for bn in (64, 128, 512):
        got = ops.gram_hessian(X, w, block_n=bn)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ fused_irls
@pytest.mark.parametrize("counts", [
    (512, 512), (300, 512), (3, 1111, 40), (1, 1)
], ids=lambda c: "x".join(map(str, c)))
@pytest.mark.parametrize("d", [6, 84, 128])
def test_fused_irls_matches_ref_ragged(counts, d):
    """One launch over ragged institutions == masked batched oracle."""
    s_dim, n_max = len(counts), max(counts)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(n_max + d), 3)
    X = jax.random.normal(k1, (s_dim, n_max, d), dtype=jnp.float64)
    y = jax.random.bernoulli(k2, 0.4, (s_dim, n_max)).astype(jnp.float64)
    beta = 0.3 * jax.random.normal(k3, (d,), dtype=jnp.float64)
    cnt = jnp.asarray(counts, jnp.int32)
    H_r, g_r, dev_r = ref.fused_irls(beta, X, y, cnt)
    for simulate in (False, True):  # real interpreted kernel + XLA sim
        H, g, dev = ops.fused_irls(beta, X, y, cnt, simulate=simulate)
        np.testing.assert_allclose(H, H_r, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(g, g_r, rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(dev, dev_r, rtol=1e-12)


def test_fused_irls_block_sweep_masks_exactly():
    """Blocked accumulation + masking is invariant to block size, including
    blocks larger than the smallest institution."""
    counts = (7, 530, 64)
    X = jax.random.normal(jax.random.PRNGKey(0), (3, 530, 12), jnp.float64)
    y = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (3, 530)).astype(
        jnp.float64
    )
    beta = 0.1 * jnp.ones((12,), jnp.float64)
    cnt = jnp.asarray(counts, jnp.int32)
    _, g_want, dev_want = ref.fused_irls(beta, X, y, cnt)
    for bn in (8, 64, 512):
        H, g, dev = ops.fused_irls(beta, X, y, cnt, block_n=bn,
                                   simulate=False)
        np.testing.assert_allclose(g, g_want, rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(dev, dev_want, rtol=1e-12)


def test_fused_irls_kernel_matches_simulation():
    """The blocked Pallas kernel and its XLA functional simulation obey
    the same numerics contract: identical g/dev (payload-dtype math) and
    f32-tolerance-identical Gram."""
    counts = (100, 512)
    X = jax.random.normal(jax.random.PRNGKey(2), (2, 512, 84), jnp.float64)
    y = jax.random.bernoulli(jax.random.PRNGKey(3), 0.3, (2, 512)).astype(
        jnp.float64
    )
    beta = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (84,), jnp.float64)
    cnt = jnp.asarray(counts, jnp.int32)
    Hk, gk, devk = ops.fused_irls(beta, X, y, cnt, block_n=128,
                                  simulate=False)
    Hs, gs, devs = ops.fused_irls(beta, X, y, cnt, simulate=True)
    np.testing.assert_allclose(Hk, Hs, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(gk, gs, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(devk, devs, rtol=1e-12)


def test_fused_irls_agrees_with_core_summaries():
    """Kernel path == the jnp path used by core.logreg, per institution."""
    from repro.core.logreg import local_summaries

    X = jax.random.normal(jax.random.PRNGKey(5), (2, 400, 20))
    y = jax.random.bernoulli(jax.random.PRNGKey(6), 0.5, (2, 400)).astype(
        jnp.float64
    )
    beta = jnp.zeros((20,), dtype=jnp.float64)
    H, g, dev = ops.fused_irls(beta, X, y)
    for j in range(2):
        s = local_summaries(beta, X[j], y[j])
        np.testing.assert_allclose(g[j], s.gradient, rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(dev[j], s.deviance, rtol=1e-12)
        np.testing.assert_allclose(H[j], s.hessian, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------- shamir_poly
@pytest.mark.parametrize("p", [P31, P31B])
@given(data=st.data())
@settings(max_examples=40, deadline=None)
def test_mulmod31_limb_decomposition(p, data):
    a = data.draw(st.integers(0, p - 1))
    b = data.draw(st.integers(0, p - 1))
    got = mulmod31(jnp.uint32(a), jnp.uint32(b), p)
    assert int(got) == (a * b) % p


@pytest.mark.parametrize("p", [P31, P31B])
def test_mulmod31_edge_cases(p):
    edges = [0, 1, 2, 0xFFFF, 0x10000, p - 1, p // 2, 2**30, 2**30 + 1]
    for a in edges:
        for b in edges:
            got = int(mulmod31(jnp.uint32(a), jnp.uint32(b), p))
            assert got == (a * b) % p, (a, b, p)


@pytest.mark.parametrize("p", [P31, P31B])
def test_mulmod31_adversarial_vs_bigint(p):
    """Vectorized sweep of limb-boundary / near-modulus operand pairs
    against Python big-int ground truth (the oracle the limb decomposition
    must reproduce exactly)."""
    corners = [
        0, 1, 2, 3,
        0x7FFF, 0x8000, 0x8001,            # 2**15 boundary (shl16 split)
        0xFFFF, 0x10000, 0x10001,          # 2**16 limb boundary
        0xFFFF_FFFF % p, (2**30 - 1), 2**30, 2**30 + 1,
        p - 1, p - 2, p - 19, p - 20,      # near the modulus
        (p - 1) // 2, (p + 1) // 2,
    ]
    a = np.asarray([x for x in corners for _ in corners], dtype=np.uint32)
    b = np.asarray(corners * len(corners), dtype=np.uint32)
    got = np.asarray(mulmod31(jnp.asarray(a), jnp.asarray(b), p))
    want = (a.astype(object) * b.astype(object)) % p  # big-int, no overflow
    np.testing.assert_array_equal(got, want.astype(np.uint32))


@pytest.mark.parametrize("p", [P31, P31B])
def test_addmod_adversarial_vs_bigint(p):
    """addmod needs reduced inputs; sweep sums that straddle p exactly."""
    corners = [0, 1, 2, 0xFFFF, 0x10000, 2**30, p // 2, p // 2 + 1,
               p - 2, p - 1]
    a = np.asarray([x for x in corners for _ in corners], dtype=np.uint32)
    b = np.asarray(corners * len(corners), dtype=np.uint32)
    got = np.asarray(addmod(jnp.asarray(a), jnp.asarray(b), p))
    want = (a.astype(object) + b.astype(object)) % p
    np.testing.assert_array_equal(got, want.astype(np.uint32))


# ----------------------------------------------------------- shamir_reconstruct
@pytest.mark.parametrize("p", [P31, P31B])
@pytest.mark.parametrize("t,w", [(2, 3), (3, 5)])
def test_shamir_reconstruct_kernel_inverts_shares(p, t, w):
    """Kernel Lagrange reconstruction inverts the share kernel exactly,
    including from non-contiguous point subsets."""
    n = 513
    k1, k2 = jax.random.split(jax.random.PRNGKey(w * 10 + t))
    secret = jax.random.randint(k1, (n,), 0, p, dtype=jnp.int64).astype(
        jnp.uint64
    )
    coeffs = jax.random.randint(
        k2, (t - 1, n), 0, p, dtype=jnp.int64
    ).astype(jnp.uint64)
    shares = ops.shamir_shares(secret, coeffs, w, p)
    subsets = [list(range(1, t + 1)), [1] + list(range(w - t + 2, w + 1))]
    for pts in subsets:
        sub = shares[jnp.asarray([q - 1 for q in pts])]
        rec = ops.shamir_reconstruct(sub, pts, p)
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(secret))


def test_shamir_reveal_flat_garner_matches_codec():
    """Fused reconstruct+CRT-decode == FixedPointCodec.decode, FIELD_WIDE."""
    from repro.core.field import FIELD_WIDE
    from repro.core.fixed_point import FixedPointCodec
    from repro.core.shamir import ShamirScheme

    codec = FixedPointCodec()
    sch = ShamirScheme(threshold=2, num_shares=3, field=FIELD_WIDE)
    rows = 8
    x = 100.0 * jax.random.normal(
        jax.random.PRNGKey(3), (rows, 128), jnp.float64
    )
    enc = codec.encode(x)  # (R, rows, 128)
    shares = sch.share(jax.random.PRNGKey(4), enc)  # (w, R, rows, 128)
    got = ops.shamir_reveal_flat(
        shares.astype(jnp.uint32), (1, 2, 3), FIELD_WIDE.moduli,
        codec.frac_bits,
    )
    want = codec.decode(sch.reconstruct(shares))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("p", [P31, P31B])
@pytest.mark.parametrize("t,w", [(2, 3), (3, 5), (5, 9)])
@pytest.mark.parametrize("n", [1, 100, 4096])
def test_shamir_kernel_matches_ref(p, t, w, n):
    k1, k2 = jax.random.split(jax.random.PRNGKey(t * 100 + n))
    secret = jax.random.randint(k1, (n,), 0, p, dtype=jnp.int64).astype(
        jnp.uint64
    )
    coeffs = jax.random.randint(
        k2, (t - 1, n), 0, p, dtype=jnp.int64
    ).astype(jnp.uint64)
    got = ops.shamir_shares(secret, coeffs, w, p)
    want = ref.shamir_shares(secret, coeffs, w, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_shamir_kernel_shares_reconstruct_via_core():
    """Kernel-produced shares must reconstruct through core.shamir."""
    from repro.core.field import FIELD31, lift_signed
    from repro.core.shamir import ShamirScheme

    p = P31
    n = 257
    vals = jnp.arange(-128, 129, dtype=jnp.int64)
    secret = lift_signed(vals, FIELD31)[0]  # (n,) uint64
    coeffs = jax.random.randint(
        jax.random.PRNGKey(9), (1, n), 0, p, dtype=jnp.int64
    ).astype(jnp.uint64)
    shares = ops.shamir_shares(secret, coeffs, 3, p)  # (3, n)
    sch = ShamirScheme(threshold=2, num_shares=3, field=FIELD31)
    rec = sch.reconstruct(shares[:, None, :], points=[1, 2, 3])
    assert (rec[0] == secret).all()
