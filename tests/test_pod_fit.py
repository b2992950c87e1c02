"""The secure fit with its institutions sharded over a pod mesh.

``StudyCoordinator(pods=4)`` runs ``fit_scan_block`` under ``shard_map``:
each device protects its own institutions' summaries and sums their
shares, the pods' share sums meet on the exact cross-chip wire, and the
reveal and the Newton solve run on every device alike.  Field sums are
exact, so the pod fit reveals what one device holding every institution
reveals, bit for bit, and follows the same Newton path.

The mesh runs in a subprocess on four forced host devices (XLA_FLAGS has
to be set before jax initializes, as in ``test_multihost.py``); one
subprocess computes every layout, and the tests read its JSON line.
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SecureCollective
from repro.core.batched_summaries import pack_partitions
from repro.core.scanfit import fit_scan_block
from repro.distributed.xla_flags import mesh_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LAYOUTS = {
    # 2 sites a pod, of unlike sizes
    "8-ragged": [30, 7, 55, 12, 40, 3, 25, 18],
    # 3, 3, 3 and 1 sites: the last pod padded with 2 empty sites
    "10-padded": [22, 9, 31, 14, 40, 6, 27, 11, 35, 19],
    # one site a pod: the pod's local sum has one addend
    "4-one-each": [26, 13, 38, 21],
}

_SCRIPT = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import repro.core.batched_summaries as bs
    from repro.core import (Institution, SecureCollective, StudyCoordinator,
                            centralized_fit)
    from repro.core.newton import _protected_tree
    from repro.distributed.multihost import pod_mesh
    from repro.distributed.sharding import POD_AXIS
    from repro.obs import metrics

    LAYOUTS = json.loads(%r)
    D = 8
    agg = SecureCollective(backend="pallas")
    wants_slices = bs._wants_slices

    def parts_of(sizes, seed):
        rng = np.random.default_rng(seed)
        beta = rng.uniform(-1.0, 1.0, D)
        parts = []
        for n in sizes:
            X = np.concatenate([np.ones((n, 1)),
                                rng.standard_normal((n, D - 1))], 1)
            y = (rng.random(n) < 1.0 / (1.0 + np.exp(-X @ beta)))
            parts.append((jnp.asarray(X), jnp.asarray(y, jnp.float64)))
        return parts

    def fit(parts, pods):
        coord = StudyCoordinator(
            [Institution(f"site{j}", X, y) for j, (X, y) in enumerate(parts)],
            lam=1.0, protect="both", aggregator=agg, seed=7, fused=True,
            rounds="scan", summaries_backend="pallas", pods=pods)
        beta = np.asarray(coord.run())
        return coord, beta

    out = {}
    for seed, (name, sizes) in enumerate(LAYOUTS.items()):
        parts = parts_of(sizes, seed)
        res = {}
        for sliced in (False, True):
            # the compiled rung's slices, cut on the CPU too
            bs._wants_slices = (
                (lambda packed, backend: backend == "pallas"
                 and packed.slices is None) if sliced else wants_slices)
            bs.pack_cache_clear()
            metrics.reset()
            one, b1 = fit(parts, 1)
            four, b4 = fit(parts, 4)
            packed = bs.pack_partitions([(X, y) for X, y in parts],
                                        backend="pallas", mesh=pod_mesh(4))
            res[f"sliced={sliced}"] = {
                "rounds": [one.iteration, four.iteration],
                "converged": [one.converged, four.converged],
                "beta_gap": float(np.max(np.abs(b1 - b4))),
                "slices": packed.slices is not None,
                "wire_bytes": metrics.get("repro_pod_wire_bytes_total"),
                "wire_bytes_want": four.iteration * agg.pod_wire_bytes(
                    D, include_count=True),
                "report_bytes": [one.reports[0].bytes_transmitted,
                                 four.reports[0].bytes_transmitted],
            }
        X = jnp.concatenate([X for X, _ in parts])
        y = jnp.concatenate([y for _, y in parts])
        gold = centralized_fit(X, y, lam=1.0)
        res["gold_gap"] = float(np.max(np.abs(b4 - np.asarray(gold.beta))))
        res["gold_tol"] = (len(sizes) + 1) / agg.codec.scale
        res["counts"] = [int(c) for c in np.asarray(packed.counts)]
        res["block_devices"] = sorted(
            s.device.id for s in packed.X.addressable_shards)
        res["block_sites"] = [int(s.data.shape[0])
                              for s in packed.X.addressable_shards]
        out[name] = res
    bs._wants_slices = wants_slices

    # the first round's revealed aggregate from the same per-site
    # summaries, all on one device and sharded over the pods
    parts = parts_of(LAYOUTS["8-ragged"], 0)
    packed = bs.pack_partitions(parts)
    sm = bs.batched_local_summaries(jnp.zeros((D,), jnp.float64), packed,
                                    backend="pallas")
    tree = _protected_tree("both", sm.hessian, sm.gradient, sm.deviance)
    tree["count"] = packed.counts.astype(jnp.float64)
    key = jax.random.PRNGKey(11)
    one = agg.secure_round_batched(key, tree)
    mesh = pod_mesh(4)
    sites = P(POD_AXIS)
    pods = jax.jit(jax.shard_map(
        lambda t: agg.secure_round_batched(key, t, axis_name=POD_AXIS),
        mesh=mesh, in_specs=sites, out_specs=P(), check_vma=False))
    four = pods(jax.device_put(tree, NamedSharding(mesh, sites)))
    out["first_round"] = {
        "bit_equal": all(np.array_equal(np.asarray(one[k]),
                                        np.asarray(four[k])) for k in one),
        "leaves": sorted(one),
        "wire_text": pods.lower(tree).compile().as_text().count(
            "all-reduce("),
    }
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def pod_runs(tmp_path_factory):
    script = tmp_path_factory.mktemp("pods") / "pod_fit.py"
    script.write_text(_SCRIPT % json.dumps(LAYOUTS))
    env = mesh_env(host_device_count=4,
                   base=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_pod_fit_matches_one_device_fit(pod_runs, layout):
    """Over 4 pods the fit takes the same rounds to the same converged
    beta as on one device, with and without the slices of X, and lies
    within the fixed-point contract of the pooled ``centralized_fit``."""
    res = pod_runs[layout]
    for sliced in (False, True):
        run = res[f"sliced={sliced}"]
        assert run["slices"] == sliced
        assert run["rounds"][0] == run["rounds"][1]
        assert run["converged"] == [True, True]
        assert run["beta_gap"] <= 1e-12
        # every executed round's wire bytes are counted, and reported
        assert run["wire_bytes"] == run["wire_bytes_want"] > 0
        assert run["report_bytes"][1] > run["report_bytes"][0]
    assert res["gold_gap"] <= res["gold_tol"]
    sizes = LAYOUTS[layout]
    per = -(-len(sizes) // 4)
    # pods in cohort order, the last ones filled up with empty sites
    assert res["counts"] == sizes + [0] * (4 * per - len(sizes))
    assert res["block_devices"] == [0, 1, 2, 3]
    assert res["block_sites"] == [per] * 4


def test_pod_round_reveals_the_one_device_aggregate_bit_for_bit(pod_runs):
    """The same per-site summaries protected on their pods and summed on
    the wire reveal exactly the field sum one device reveals, with one
    all-reduce over the pods."""
    first = pod_runs["first_round"]
    assert first["leaves"] == ["count", "deviance", "gradient", "hessian"]
    assert first["bit_equal"]
    assert first["wire_text"] == 1


_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
                "collective-permute")


def test_one_device_fit_program_has_no_collective_or_sharding():
    """``pods=1`` passes no mesh: the fit program XLA compiles for one
    device holds no collective and the program carries no sharding
    annotation."""
    rng = np.random.default_rng(0)
    parts = [(jnp.asarray(rng.standard_normal((n, 4))),
              jnp.asarray((rng.random(n) < 0.5).astype(np.float64)))
             for n in (9, 14, 5)]
    packed = pack_partitions(parts)
    lowered = fit_scan_block.lower(
        jnp.zeros(4, jnp.float64), jnp.asarray(np.inf), jnp.asarray(False),
        jnp.zeros((), jnp.int32), jax.random.PRNGKey(0),
        jnp.zeros((), jnp.int32), packed.X, packed.X32, packed.slices,
        packed.y, packed.counts, jnp.asarray(1.0),
        agg=SecureCollective(backend="pallas"), protect="both", l1=0.0,
        tol=1e-10, points=(1, 2), include_count=True,
        summaries_backend="pallas", num_rounds=2, num_parts=3,
        max_rounds=2)
    compiled = lowered.compile().as_text()
    assert not [c for c in _COLLECTIVES if c + "(" in compiled]
    # a custom call's ``sdy.sharding_rule`` says how it would split, not
    # that anything is split
    assert not re.search(
        r"mhlo\.sharding|sdy\.sharding(?!_rule)|manual_computation|"
        r"@Sharding|shard_map", lowered.as_text())


def test_importing_core_leaves_the_mesh_launcher_unimported():
    """The one-chip path pays for no multi-chip module at import."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, repro.core; "
         "print('repro.distributed.multihost' in sys.modules)"],
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")),
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False"


def test_the_wire_bytes_of_a_round():
    """One device hands the wire the aggregated 2-of-3 share buffer of
    the fully protected d = 128 tree: 16,514 field elements in 136 rows
    of 128 lanes, two residues, three slices, each element as two
    uint32 limbs; the round's bytes add every pod's."""
    agg = SecureCollective(backend="pallas")
    per_device = 3 * 2 * 136 * 128 * 2 * 4
    assert agg.pod_wire_bytes(128, include_count=True) == per_device
    one = agg.round_bytes(128, 208, "both", include_count=True)
    four = agg.round_bytes(128, 208, "both", include_count=True, pods=4)
    assert four - one == 4 * per_device
