"""The work counts against counts worked out by hand at two shapes."""
import pytest

from bench import work


@pytest.mark.parametrize("rows,sites,d,flops,kernel_bytes", [
    # 2*N*d^2 + 4*N*d (+ d^3/3 for the round); bytes: X, y, beta read,
    # H, g, dev per site written, all 4 bytes
    (1000, 2, 4, 2 * 1000 * 16 + 4 * 1000 * 4,
     4 * (1000 * 4 + 1000 + 4) + 4 * (2 * 16 + 2 * 4 + 2)),
    (200_000, 8, 128, 2 * 200_000 * 128 ** 2 + 4 * 200_000 * 128,
     4 * (200_000 * 128 + 200_000 + 128) + 4 * (8 * 128 ** 2 + 8 * 128 + 8)),
])
def test_irls_kernel_and_round(rows, sites, d, flops, kernel_bytes):
    f, b = work.irls_kernel(rows, sites, d)
    assert f == flops
    assert b == kernel_bytes
    assert work.fit_round_flops(rows, d) == pytest.approx(flops + d ** 3 / 3)


@pytest.mark.parametrize("sites,d,share,recon", [
    # per site d^2 + d + 2 elements, 2 residues, 2-of-3: protect reads the
    # secret and 1 coefficient and writes 3 shares; reveal reads 2 shares
    # and writes the residues
    (6, 6, 4 * 6 * 44 * 2 * (1 + 1 + 3), 4 * 44 * 2 * 3),
    (8, 128, 4 * 8 * 16514 * 2 * (1 + 1 + 3), 4 * 16514 * 2 * 3),
])
def test_shamir_kernels(sites, d, share, recon):
    assert work.protected_elements(d, "both") == d * d + d + 2
    assert work.share_kernel(sites, d, "both", 2, 2, 3) == share
    assert work.reconstruct_kernel(d, "both", 2, 2) == recon
