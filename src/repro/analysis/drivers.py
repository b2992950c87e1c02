"""Driver specs: which secure graphs the gate traces, and their taints.

Each :class:`DriverSpec` names one secure driver round graph, builds its
closed jaxpr on tiny synthetic shapes (``jax.make_jaxpr`` — no kernel
ever executes, Pallas included), and labels every flat input with its
taint.  The secure drivers map to these specs:

* ``secure_fit_fused``   — ``SecureFitDriver.step``'s fused round
  (``newton._fused_secure_iteration``).
* ``coordinator_fused``  — the same graph in ``StudyCoordinator.step``
  fused trim (``include_count=True``, the coordinator wire tree).
* ``secure_fit_scan``    — ``rounds="scan"``'s whole-block graph
  (``scanfit.fit_scan_block``), shared by driver and coordinator.
* ``pod_fit_scan``       — the same block with the institutions sharded
  over a pod mesh of 4 (``StudyCoordinator(pods=4)``), traced through
  ``shard_map`` over an AbstractMesh: the pods' share sums cross the
  wire, the reveal is replicated.
* ``selection_scan``     — the CV sweep's multi-config scan body
  (``selection.path._cv_sweep_block``).
* ``secure_psum_replicated`` / ``secure_psum_sharded`` /
  ``secure_psum_tile`` — the 1D SPMD wire in all reveal/out modes,
  traced through ``shard_map`` over an **AbstractMesh** (no devices
  needed; the mesh's axis sizes feed the collective taint rules).
* ``secure_psum_2d``     — the (pod, share) mesh with the distributed
  Lagrange reveal.

Fused specs trace twice — ``protect="both"`` (everything shared) and
``protect="gradient"`` (the paper's pragmatic mode, exercising the
``declassify_sum`` plaintext-aggregation annotation).

Every spec's graph routes through the ONE
:class:`repro.core.collective.SecureCollective` chain, so the named
boundary jits the taint rules key on (``_protect_flat`` /
``_reveal_flat`` / ``_distributed_reveal`` / ``declassify_sum``) are the
same four call sites the runtime ledger hooks and the byte telemetry
account — certifying a driver here certifies the only chain it can use.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .taint import PUBLIC, SECRET

__all__ = ["DriverSpec", "all_driver_specs", "toy_parts"]


@dataclasses.dataclass(frozen=True)
class DriverSpec:
    """One traced driver graph + the taint labels of its flat inputs."""

    name: str
    build: Callable  # () -> (closed_jaxpr, flat_in_taints)
    threshold: int
    # mesh axis sizes known OUTSIDE any shard_map in the traced graph
    # (shard_map eqns push their own mesh's sizes during the walk)
    axis_sizes: dict = dataclasses.field(default_factory=dict)
    # runnable form for the RUNTIME audit (``python -m repro.obs audit``):
    # () -> None, executes the same driver graph on the same toy shapes
    # so the privacy ledger's recorded counts can be reconciled against
    # the static census of the built jaxpr.  None: spec is trace-only.
    runner: Callable | None = None
    # real devices the runner needs (psum specs trace on an AbstractMesh
    # but execute on a concrete one)
    min_devices: int = 1


def toy_parts(num_parts: int = 3, n: int = 8, d: int = 4):
    """Tiny deterministic partitions (no rng: specs must be stable)."""
    parts = []
    for j in range(num_parts):
        base = np.arange(n * d, dtype=np.float64).reshape(n, d)
        X = np.tanh((base + j) / (n * d))
        y = ((base.sum(axis=1) + j) % 2).astype(np.float64)
        parts.append((jnp.asarray(X), jnp.asarray(y)))
    return parts


def _aggregator():
    from ..core.collective import SecureCollective

    return SecureCollective(backend="pallas")


def _packed(num_parts=3, n=8, d=4):
    """The toy pack, with the digit slices the compiled ``pallas`` rung
    reads cut here too, so every spec traces the path that reads them."""
    import dataclasses

    from ..core.batched_summaries import pack_partitions
    from ..kernels.sliced_terms import cut_slices

    packed = pack_partitions(toy_parts(num_parts, n, d))
    return dataclasses.replace(packed, slices=cut_slices(packed.X))


def _fused_spec(name: str, protect: str, include_count: bool):
    def build():
        from ..core.newton import _fused_secure_iteration

        agg = _aggregator()
        packed = _packed()
        beta = jnp.zeros((packed.dim,), jnp.float64)
        key = jax.random.PRNGKey(0)

        def fn(beta, key, X, X32, slices, y, counts):
            return _fused_secure_iteration(
                beta, key, X, X32, slices, y, counts, 1.0, agg, protect,
                0.0, points=None, include_count=include_count,
                summaries_backend="pallas",
            )

        closed = jax.make_jaxpr(fn)(
            beta, key, packed.X, packed.X32, packed.slices, packed.y,
            packed.counts,
        )
        # the slices (digits, scale) are X's, so secret like X
        taints = [PUBLIC, PUBLIC] + [SECRET] * 6
        return closed, taints

    def runner():
        from ..core.newton import _fused_secure_iteration

        agg = _aggregator()
        packed = _packed()
        beta = jnp.zeros((packed.dim,), jnp.float64)
        out = _fused_secure_iteration(
            beta, jax.random.PRNGKey(0), packed.X, packed.X32,
            packed.slices, packed.y, packed.counts, 1.0, agg, protect, 0.0,
            points=None,
            include_count=include_count, summaries_backend="pallas",
        )
        jax.block_until_ready(out)

    return DriverSpec(name=name, build=build, runner=runner,
                      threshold=_aggregator().scheme.threshold)


def _scan_spec(name: str, protect: str, include_count: bool):
    def build():
        from ..core.scanfit import fit_scan_block

        agg = _aggregator()
        packed = _packed()
        beta = jnp.zeros((packed.dim,), jnp.float64)
        key = jax.random.PRNGKey(0)

        def fn(beta, obj_prev, conv, iters, key, rbase,
               X, X32, slices, y, counts):
            return fit_scan_block(
                beta, obj_prev, conv, iters, key, rbase,
                X, X32, slices, y, counts, 1.0,
                agg=agg, protect=protect, l1=0.0, tol=1e-10,
                points=None,
                include_count=include_count,
                summaries_backend="pallas", num_rounds=3,
                num_parts=packed.num_institutions, max_rounds=3,
            )

        closed = jax.make_jaxpr(fn)(
            beta, jnp.asarray(np.inf), jnp.asarray(False),
            jnp.zeros((), jnp.int32), key, jnp.zeros((), jnp.int32),
            packed.X, packed.X32, packed.slices, packed.y, packed.counts,
        )
        taints = [PUBLIC] * 6 + [SECRET] * 6
        return closed, taints

    def runner():
        from ..core.scanfit import fit_scan_block

        agg = _aggregator()
        packed = _packed()
        beta = jnp.zeros((packed.dim,), jnp.float64)
        out = fit_scan_block(
            beta, jnp.asarray(np.inf), jnp.asarray(False),
            jnp.zeros((), jnp.int32), jax.random.PRNGKey(0),
            jnp.zeros((), jnp.int32),
            packed.X, packed.X32, packed.slices, packed.y, packed.counts,
            1.0, agg=agg, protect=protect, l1=0.0, tol=1e-10,
            points=None, include_count=include_count,
            summaries_backend="pallas", num_rounds=3,
            num_parts=packed.num_institutions, max_rounds=3,
        )
        jax.block_until_ready(out)

    return DriverSpec(name=name, build=build, runner=runner,
                      threshold=_aggregator().scheme.threshold)


def _pod_scan_spec(name: str, num_pods: int = 4):
    """The scan fit with its institutions sharded over a pod mesh
    (``fit_scan_block(mesh=)``): two toy institutions a pod, traced over
    an AbstractMesh, executed over the real one by the runner."""
    def args(packed):
        return (jnp.zeros((packed.dim,), jnp.float64), jnp.asarray(np.inf),
                jnp.asarray(False), jnp.zeros((), jnp.int32),
                jax.random.PRNGKey(0), jnp.zeros((), jnp.int32),
                packed.X, packed.X32, packed.slices, packed.y,
                packed.counts)

    def fit(mesh, *a):
        from ..core.scanfit import fit_scan_block

        return fit_scan_block(
            *a, 1.0, agg=_aggregator(), protect="both", l1=0.0, tol=1e-10,
            points=None, include_count=True, summaries_backend="pallas",
            num_rounds=3, num_parts=2 * num_pods, max_rounds=3, mesh=mesh,
        )

    def build():
        from jax.sharding import AbstractMesh

        from ..distributed.sharding import POD_AXIS

        mesh = AbstractMesh((num_pods,), (POD_AXIS,))
        closed = jax.make_jaxpr(lambda *a: fit(mesh, *a))(
            *args(_packed(2 * num_pods)))
        # X, X32, the slices (digits, scale), y and counts of every pod
        taints = [PUBLIC] * 6 + [SECRET] * 6
        return closed, taints

    def runner():
        from ..core.batched_summaries import _with_slices, pack_partitions
        from ..distributed.multihost import pod_mesh

        mesh = pod_mesh(num_pods)
        packed = _with_slices(
            pack_partitions(toy_parts(2 * num_pods), mesh=mesh), None, mesh)
        jax.block_until_ready(fit(mesh, *args(packed)))

    return DriverSpec(name=name, build=build, runner=runner,
                      min_devices=num_pods,
                      threshold=_aggregator().scheme.threshold)


def _selection_spec(name: str, protect: str):
    def build():
        from ..selection.folds import assign_folds, pack_fold_ids
        from ..selection.path import _cv_sweep_block

        agg = _aggregator()
        num_parts, n, d, num_folds = 3, 8, 4, 2
        packed = _packed(num_parts, n, d)
        fold_parts = [
            assign_folds(n, num_folds, j, 0) for j in range(num_parts)
        ]
        fold_ids = pack_fold_ids(fold_parts, packed.X.shape[1])
        lam_grid = (1.0, 0.5)
        cfg = len(lam_grid) * num_folds
        lams = jnp.asarray(np.repeat(lam_grid, num_folds), jnp.float64)
        fold_of = jnp.asarray(
            np.tile(np.arange(num_folds, dtype=np.int32), len(lam_grid))
        )
        key = jax.random.PRNGKey(0)

        def fn(betas, obj_prev, conv, iters, vdev, vcorr, vcnt, key,
               rbase, X, X32, y, counts, fold_ids, fold_of, lams):
            return _cv_sweep_block(
                betas, obj_prev, conv, iters, vdev, vcorr, vcnt, key,
                rbase, X, X32, y, counts, fold_ids, fold_of, lams,
                agg=agg, protect=protect, l1=0.0, tol=1e-10,
                points=None,
                summaries_backend="pallas", num_rounds=2,
                num_parts=packed.num_institutions, max_rounds=2,
            )

        closed = jax.make_jaxpr(fn)(
            jnp.zeros((cfg, d), jnp.float64),
            jnp.full((cfg,), np.inf, jnp.float64),
            jnp.zeros((cfg,), bool),
            jnp.zeros((cfg,), jnp.int32),
            jnp.zeros((cfg,), jnp.float64),
            jnp.zeros((cfg,), jnp.float64),
            jnp.zeros((cfg,), jnp.float64),
            key, jnp.zeros((), jnp.int32),
            packed.X, packed.X32, packed.y, packed.counts,
            fold_ids, fold_of, lams,
        )
        # fold ids are institution-local row metadata: SECRET like the
        # rows they index; the config->fold map and the λ grid are public
        taints = [PUBLIC] * 9 + [SECRET] * 5 + [PUBLIC, PUBLIC]
        return closed, taints

    def runner():
        from ..selection.folds import assign_folds, pack_fold_ids
        from ..selection.path import _cv_sweep_block

        agg = _aggregator()
        num_parts, n, d, num_folds = 3, 8, 4, 2
        packed = _packed(num_parts, n, d)
        fold_parts = [
            assign_folds(n, num_folds, j, 0) for j in range(num_parts)
        ]
        fold_ids = pack_fold_ids(fold_parts, packed.X.shape[1])
        lam_grid = (1.0, 0.5)
        cfg = len(lam_grid) * num_folds
        lams = jnp.asarray(np.repeat(lam_grid, num_folds), jnp.float64)
        fold_of = jnp.asarray(
            np.tile(np.arange(num_folds, dtype=np.int32), len(lam_grid))
        )
        out = _cv_sweep_block(
            jnp.zeros((cfg, d), jnp.float64),
            jnp.full((cfg,), np.inf, jnp.float64),
            jnp.zeros((cfg,), bool),
            jnp.zeros((cfg,), jnp.int32),
            jnp.zeros((cfg,), jnp.float64),
            jnp.zeros((cfg,), jnp.float64),
            jnp.zeros((cfg,), jnp.float64),
            jax.random.PRNGKey(0), jnp.zeros((), jnp.int32),
            packed.X, packed.X32, packed.y, packed.counts,
            fold_ids, fold_of, lams,
            agg=agg, protect=protect, l1=0.0, tol=1e-10,
            points=None,
            summaries_backend="pallas", num_rounds=2,
            num_parts=packed.num_institutions, max_rounds=2,
        )
        jax.block_until_ready(out)

    return DriverSpec(name=name, build=build, runner=runner,
                      threshold=_aggregator().scheme.threshold)


def _toy_tree(d: int = 12):
    g = np.linspace(-1.0, 1.0, d)
    return {
        "gradient": jnp.asarray(g),
        "bias": jnp.asarray(g[:4].reshape(2, 2) * 0.5),
    }


def _psum_spec(name: str, reveal: str, out: str, num_pods: int = 4):
    def build():
        from jax.sharding import AbstractMesh, PartitionSpec as P

        from ..core.collective import secure_psum
        from ..distributed.sharding import POD_AXIS

        agg = _aggregator()
        key = jax.random.PRNGKey(0)
        mesh = AbstractMesh((num_pods,), (POD_AXIS,))
        fn = jax.shard_map(
            lambda tree: secure_psum(
                tree, POD_AXIS, key, aggregator=agg, reveal=reveal,
                out=out,
            ),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
        )
        tree = _toy_tree()
        closed = jax.make_jaxpr(fn)(tree)
        taints = [SECRET] * len(jax.tree_util.tree_leaves(tree))
        return closed, taints

    def runner():
        from jax.sharding import PartitionSpec as P

        from ..core.collective import secure_psum
        from ..distributed.multihost import pod_mesh
        from ..distributed.sharding import POD_AXIS

        agg = _aggregator()
        key = jax.random.PRNGKey(0)
        mesh = pod_mesh(num_pods)
        fn = jax.jit(jax.shard_map(
            lambda tree: secure_psum(
                tree, POD_AXIS, key, aggregator=agg, reveal=reveal,
                out=out,
            ),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
        ))
        jax.block_until_ready(fn(_toy_tree()))

    return DriverSpec(name=name, build=build, runner=runner,
                      min_devices=num_pods,
                      threshold=_aggregator().scheme.threshold)


def _psum_2d_spec(name: str, num_pods: int = 3):
    def build():
        from jax.sharding import AbstractMesh, PartitionSpec as P

        from ..distributed.multihost import secure_psum_2d
        from ..distributed.sharding import POD_AXIS, SHARE_AXIS

        agg = _aggregator()
        key = jax.random.PRNGKey(0)
        # one share column per reveal point: share axis == threshold
        mesh = AbstractMesh(
            (num_pods, agg.scheme.threshold), (POD_AXIS, SHARE_AXIS)
        )
        fn = jax.shard_map(
            lambda tree: secure_psum_2d(tree, key, aggregator=agg),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
        )
        tree = _toy_tree()
        closed = jax.make_jaxpr(fn)(tree)
        taints = [SECRET] * len(jax.tree_util.tree_leaves(tree))
        return closed, taints

    def runner():
        from jax.sharding import PartitionSpec as P

        from ..distributed.multihost import pod_share_mesh, secure_psum_2d

        agg = _aggregator()
        key = jax.random.PRNGKey(0)
        mesh = pod_share_mesh(num_pods, agg.scheme.threshold)
        fn = jax.jit(jax.shard_map(
            lambda tree: secure_psum_2d(tree, key, aggregator=agg),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
        ))
        jax.block_until_ready(fn(_toy_tree()))

    return DriverSpec(name=name, build=build, runner=runner,
                      min_devices=num_pods * _aggregator().scheme.threshold,
                      threshold=_aggregator().scheme.threshold)


def all_driver_specs() -> list:
    """Every graph the standing gate certifies, in gate order."""
    return [
        _fused_spec("secure_fit_fused[protect=both]", "both", False),
        _fused_spec("secure_fit_fused[protect=gradient]", "gradient",
                    False),
        _fused_spec("coordinator_fused[protect=both]", "both", True),
        _fused_spec("coordinator_fused[protect=gradient]", "gradient",
                    True),
        _scan_spec("secure_fit_scan[protect=both]", "both", False),
        _scan_spec("secure_fit_scan[protect=gradient]", "gradient",
                   False),
        _pod_scan_spec("pod_fit_scan[pods=4]"),
        _selection_spec("selection_scan[protect=both]", "both"),
        _selection_spec("selection_scan[protect=gradient]", "gradient"),
        _psum_spec("secure_psum[replicated]", "replicated", "tree"),
        _psum_spec("secure_psum[sharded,tree]", "sharded", "tree"),
        _psum_spec("secure_psum[sharded,tile]", "sharded", "tile"),
        _psum_2d_spec("secure_psum_2d"),
    ]
