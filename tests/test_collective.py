"""The unified SecureCollective: one chain, many consumers.

PR 10 folded the four protect -> aggregate -> reveal chains (secure_fit,
StudyCoordinator, the selection sweep, secure_psum/psum_2d) onto ONE
:class:`repro.core.collective.SecureCollective`.  The lockstep tests in
test_secure_pipeline / test_scan_rounds / test_selection / test_multihost
pin bit-parity of the existing consumers; this module pins the NEW
surface:

* the compat alias (``SecureAggregator`` IS ``SecureCollective`` — one
  class, one jit key-space),
* the one byte model behind every driver's telemetry,
* the first genuinely new consumer: slot-packed multi-study rounds
  (:mod:`repro.core.multistudy`) matching independent per-study fits to
  fixed-point quantization — including ragged studies entering via
  count=0 padding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SecureAggregator, SecureCollective
from repro.core.batched_summaries import batched_local_summaries, pack_partitions
from repro.core.multistudy import (
    fused_multistudy_iteration,
    run_multistudy_rounds,
    stack_studies,
)
from repro.core.newton import _fused_secure_iteration, _iteration_bytes
from repro.data import generate_synthetic

NUM_INST = 4
DIM = 5


@pytest.fixture(scope="module")
def agg():
    return SecureCollective(backend="pallas")


@pytest.fixture(scope="module")
def studies():
    """Two independent cohorts, same feature space, different data."""
    return [
        generate_synthetic(jax.random.PRNGKey(11), num_institutions=NUM_INST,
                           records_per_institution=120, dim=DIM),
        generate_synthetic(jax.random.PRNGKey(23), num_institutions=NUM_INST,
                           records_per_institution=120, dim=DIM),
    ]


def quant_tol(agg, num_parts=NUM_INST):
    return (num_parts + 1) / agg.codec.scale


# ------------------------------------------------------------- compat alias

def test_aggregator_is_collective_alias():
    """One class: the historical name must not fork the jit key-space."""
    assert SecureAggregator is SecureCollective


def test_round_bytes_is_the_one_model(agg):
    """The newton shim and the method agree — a single size model."""
    for protect in ("none", "gradient", "hessian", "both"):
        assert _iteration_bytes(DIM, NUM_INST, protect, agg) \
            == agg.round_bytes(DIM, NUM_INST, protect)
    # the coordinator/selection variants are the same model, parameterized
    # (row alignment may absorb the extra count scalar, hence >=)
    assert agg.round_bytes(DIM, NUM_INST, "both", include_count=True) \
        >= agg.round_bytes(DIM, NUM_INST, "both")
    assert agg.round_bytes(DIM, NUM_INST, "both", num_configs=3) \
        == 3 * agg.round_bytes(DIM, NUM_INST, "both")


# ------------------------------------------- multiconfig wire: slot parity

def test_multiconfig_round_slots_bit_equal_per_study(agg, studies):
    """Each slot of the ONE multiconfig reveal is bit-equal to that
    study's own batched round: Shamir reconstruction cancels the sharing
    polynomials exactly, and slots are independent payload lanes."""
    key = jax.random.PRNGKey(0)
    trees = []
    for study in studies:
        packed = pack_partitions(study.parts)
        beta0 = jnp.zeros((DIM,), jnp.float64)
        sm = batched_local_summaries(beta0, packed, backend="pallas")
        trees.append({"gradient": sm.gradient, "hessian": sm.hessian,
                      "deviance": sm.deviance})
    stacked = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *trees)  # (M, S, ...)
    multi = agg.secure_round_multiconfig(key, stacked)
    for m, tree in enumerate(trees):
        solo = agg.secure_round_batched(jax.random.fold_in(key, m), tree)
        for leaf in tree:
            np.testing.assert_array_equal(
                np.asarray(multi[leaf][m]), np.asarray(solo[leaf]),
                err_msg=f"slot {m} leaf {leaf}")


# ------------------------------------------------- multi-study == M x solo

@pytest.mark.parametrize("protect", ["none", "gradient", "both"])
def test_multistudy_iteration_matches_independent(agg, studies, protect):
    """One slot-packed round == two independent fused rounds, per study,
    to fixed-point quantization (revealed aggregates are bit-equal; the
    batched Newton tail may differ in low-order solve bits)."""
    key = jax.random.PRNGKey(7)
    lams = (1.0, 0.3)
    packed = stack_studies([s.parts for s in studies])
    betas0 = jnp.zeros((len(studies), DIM), jnp.float64)
    betas, objs, gnorms, snorms = fused_multistudy_iteration(
        betas0, key, packed.X, packed.X32, packed.y, packed.counts,
        jnp.asarray(lams, jnp.float64), agg, protect, 0.0,
    )
    tol = quant_tol(agg)
    for m, study in enumerate(studies):
        p = pack_partitions(study.parts)
        b_ref, obj_ref, g_ref, s_ref = _fused_secure_iteration(
            betas0[m], jax.random.fold_in(key, m), p.X, p.X32, p.y,
            p.counts, lams[m], agg, protect, 0.0,
        )
        assert np.abs(np.asarray(betas[m]) - np.asarray(b_ref)).max() <= tol
        assert abs(float(objs[m]) - float(obj_ref)) <= tol * NUM_INST
        assert abs(float(gnorms[m]) - float(g_ref)) <= tol * DIM
        assert abs(float(snorms[m]) - float(s_ref)) <= tol * DIM


def test_multistudy_rounds_track_independent_fits(agg, studies):
    """Three slot-packed rounds track three per-study fused rounds: the
    packed trajectory stays within quantization of the solo trajectory
    at every round, for every study."""
    lams = (1.0, 0.3)
    num_rounds = 3
    betas, trace = run_multistudy_rounds(
        [s.parts for s in studies], lams, num_rounds, aggregator=agg,
        protect="both",
    )
    assert trace.shape == (num_rounds, len(studies))
    tol = quant_tol(agg)
    key = jax.random.PRNGKey(0)
    for m, study in enumerate(studies):
        p = pack_partitions(study.parts)
        beta = jnp.zeros((DIM,), jnp.float64)
        for r in range(num_rounds):
            beta, obj, _, _ = _fused_secure_iteration(
                beta, jax.random.fold_in(key, r), p.X, p.X32, p.y,
                p.counts, lams[m], agg, "both", 0.0,
            )
            # per-round quantization errors can compound through the
            # Newton updates; allow one tol per elapsed round
            assert abs(float(trace[r, m]) - float(obj)) \
                <= tol * NUM_INST * (r + 1)
        assert np.abs(np.asarray(betas[m]) - np.asarray(beta)).max() \
            <= tol * num_rounds


def test_ragged_studies_pad_with_silent_institutions(agg):
    """A narrower cohort enters the packed round via count=0 padding and
    still matches its own independent round: zero-count institutions
    encode to the zero field element and vanish from every aggregate."""
    wide = generate_synthetic(jax.random.PRNGKey(3), num_institutions=4,
                              records_per_institution=100, dim=DIM)
    slim = generate_synthetic(jax.random.PRNGKey(5), num_institutions=2,
                              records_per_institution=60, dim=DIM)
    packed = stack_studies([wide.parts, slim.parts])
    assert packed.X.shape[:2] == (2, 4)  # padded to the widest cohort
    key = jax.random.PRNGKey(9)
    betas0 = jnp.zeros((2, DIM), jnp.float64)
    betas, _, _, _ = fused_multistudy_iteration(
        betas0, key, packed.X, packed.X32, packed.y, packed.counts,
        jnp.asarray([0.5, 0.5], jnp.float64), agg, "both", 0.0,
    )
    tol = quant_tol(agg)
    for m, study in enumerate((wide, slim)):
        p = pack_partitions(study.parts)
        b_ref, *_ = _fused_secure_iteration(
            betas0[m], jax.random.fold_in(key, m), p.X, p.X32, p.y,
            p.counts, 0.5, agg, "both", 0.0,
        )
        assert np.abs(np.asarray(betas[m]) - np.asarray(b_ref)).max() <= tol
