"""Batched per-institution summaries: the local phase without the S loop.

``newton.secure_fit`` originally looped Python-side over the S institutions,
dispatching one ``local_summaries`` per partition per Newton iteration.
This module packs the ragged partitions ONCE per fit into a stacked
(S, N_max, d) layout with row masks and computes every institution's
(H_j, g_j, dev_j) in a single batched launch per iteration:

* ``backend="pallas"`` — one ``kernels.fused_irls`` launch for all S
  institutions (X streamed through VMEM once; IRLS weights never touch
  HBM; Gram accumulation in f32 as on the MXU).
* ``backend="reference"`` — the masked jnp oracle (f64 end to end), used
  by tests and as the legacy-comparable gold path; compiled for a chip
  with a float64 payload it takes the same float64 summaries from the
  pack's digit slices (``kernels.sliced_terms``).
* ``backend="mixed"`` — f64 gradient/deviance with a split-accumulation
  f32 Gram (chunked f32 gemms merged in f64): ~4x the Hessian accuracy
  of the single-pass f32 Gram at f32-gemm speed, the natural two-pass
  variant for the TPU kernel at production N.

Padding contract: rows >= counts[s] are zero AND masked in-kernel, so the
stacked layout is exact for arbitrarily uneven partitions (including an
institution smaller than one kernel block).  The packed arrays are the
per-fit constants; only beta changes across iterations, which is what
lets the whole Newton step stay jit-resident.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import weakref
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from typing import NamedTuple

from ..kernels.sliced_terms import (
    MAX_DIM,
    MAX_PAIR_DIM,
    XSlices,
    cut_pair_slices,
    cut_slices,
    sliced_gram,
    sliced_terms,
)
from ..obs import metrics as _metrics
from .logreg import LocalSummaries

__all__ = ["PackedPartitions", "pack_partitions", "batched_local_summaries",
           "CVSummaries", "batched_cv_summaries",
           "pack_cache_clear", "pack_cache_evict", "pack_cache_len"]

BACKENDS = ("reference", "pallas", "mixed")


@dataclasses.dataclass(frozen=True)
class PackedPartitions:
    """Stacked ragged partitions + the static facts the kernels need.

    ``X``/``y`` are zero-padded to (S, N_max, d); ``X32`` is the pre-cast
    f32 MXU operand for the Gram matmul (cast once per fit, not per
    iteration).  With a float32 payload — the TPU storage dtype, and what
    the fused ``secure_fit`` packs — ``X`` and ``X32`` are the SAME
    array; with float64 (the oracle/test payload) both live side by
    side.  ``y`` stays f64 either way: labels are 0/1 (exact in any
    float) and the gradient/deviance accumulate in f64.  ``slices`` are
    the bf16 digit slices of a float64 ``X`` (``kernels.sliced_terms``),
    from which the compiled ``pallas`` and ``reference`` rungs take their
    float64 gradient and deviance, and the ``reference`` rung its Gram
    from the slices of X's pair design (``slices.pair``);
    ``pack_partitions`` cuts them only for those rungs.
    """

    X: jnp.ndarray  # (S, N_max, d) payload (f32 or f64)
    X32: jnp.ndarray  # (S, N_max, d) float32 MXU operand
    y: jnp.ndarray  # (S, N_max) float64
    counts: jnp.ndarray  # (S,) int32 true row counts
    slices: XSlices | None = None  # digit slices of X, or None

    @property
    def num_institutions(self) -> int:
        return self.X.shape[0]

    @property
    def dim(self) -> int:
        return self.X.shape[2]

    @property
    def total_records(self) -> int:
        return int(np.sum(np.asarray(self.counts)))


@functools.partial(jax.jit, static_argnames=("n_max", "dtype"))
def _stack_pad(xs, ys, n_max: int, dtype):
    """One graph for pad + stack of the payload and the labels."""
    Xs = jnp.stack([
        jnp.pad(jnp.asarray(X, dtype), ((0, n_max - X.shape[0]), (0, 0)))
        for X in xs
    ])
    ys_ = jnp.stack([
        jnp.pad(jnp.asarray(y, jnp.float64), (0, n_max - y.shape[0]))
        for y in ys
    ])
    return Xs, ys_


# The f32 MXU-operand cast is a program of its own: emitted in the same
# graph as the float64 pad + stack, XLA:CPU (jax 0.9) fused the two
# outputs into one loop that read the wrong rows and left padding
# uninitialized for some ragged shapes (e.g. 6/52/53 rows of width 4).
_to_f32 = jax.jit(lambda X: X.astype(jnp.float32))


# LRU pack cache for pack_partitions.  jax arrays are immutable, so the
# identity of every part buffer is a sound cache key as long as no id is
# recycled behind the cache's back.  Each entry therefore holds a weakref
# to every part buffer whose finalizer evicts the entry the moment any
# referent is collected — a recycled id can never alias a dead buffer, and
# the cache pins no input arrays (only the packed outputs, bounded by
# ``_PACK_CACHE_SIZE`` entries).  Multiple slots serve alternating
# multi-study workloads (coordinator cohorts that churn and churn back,
# lambda sweeps over several studies) without thrashing repacks, the same
# way the jit cache serves multiple traced shapes.
_PACK_CACHE: "collections.OrderedDict[tuple, tuple[list, PackedPartitions]]" \
    = collections.OrderedDict()
# Entry bound, not a byte bound: each entry pins one packed study (f64
# payload + f32 MXU copy — hundreds of MB at benchmark scale), so the
# bound IS the residency ceiling.  4 covers the alternation patterns
# that motivated the LRU (two studies ping-ponging, a churned cohort
# plus its churn-back, a lambda sweep over a pair) at 4x the old
# single-slot ceiling; entries also die early via the weakref
# finalizers when their study's buffers are released.
_PACK_CACHE_SIZE = 4


def _pack_cache_key(parts, dtype) -> tuple:
    return (
        tuple((id(Xj), id(yj)) for Xj, yj in parts), jnp.dtype(dtype).name
    )


def pack_cache_clear():
    """Drop every cached pack (packed buffers become collectable)."""
    _PACK_CACHE.clear()


def pack_cache_evict(parts, dtype=None):
    """Evict any cached pack that includes one of ``parts``' buffers.

    Institution-churn hook: a coordinator that adds/removes an institution
    calls this with the churned partition so no later cohort can resurrect
    a stale padded batch through a recycled buffer id (the weakref
    finalizers already cover collected buffers; this covers live ones
    leaving a cohort).  ``dtype=None`` evicts across payload dtypes.
    """
    ids = {id(b) for part in parts for b in part}
    for key in list(_PACK_CACHE):
        part_ids, dt_name = key[:2]
        if dtype is not None and dt_name != jnp.dtype(dtype).name:
            continue
        if any(i in ids or j in ids for i, j in part_ids):
            _PACK_CACHE.pop(key, None)


def pack_cache_len() -> int:
    return len(_PACK_CACHE)


def _wants_pair(packed: PackedPartitions, backend: str | None) -> bool:
    """The ``reference`` rung takes its Gram from the slices of the pair
    design while that fits a row of digits, and the pack has none."""
    return (backend == "reference" and packed.dim <= MAX_PAIR_DIM
            and (packed.slices is None or packed.slices.pair is None))


def _wants_slices(packed: PackedPartitions, backend: str | None) -> bool:
    """The compiled ``pallas`` or ``reference`` rung with a float64
    payload of at most ``MAX_DIM`` features reads slices the pack does
    not carry yet."""
    from ..kernels.backend import interpret_kernels

    return (backend in ("pallas", "reference")
            and (packed.slices is None or _wants_pair(packed, backend))
            and packed.X.dtype == jnp.float64 and packed.dim <= MAX_DIM
            and not interpret_kernels())


def _with_slices(packed: PackedPartitions, key, backend: str | None,
                 mesh=None) -> PackedPartitions:
    """``packed`` with the slices ``backend`` reads cut, in its cache
    entry too.  On a pod ``mesh`` each device cuts the slices of its own
    institutions."""
    def cut(fn):
        if mesh is None:
            return fn(packed.X)
        from jax.sharding import PartitionSpec as P

        from ..distributed.sharding import POD_AXIS

        sites = P(POD_AXIS)
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=sites, out_specs=sites,
        ))(packed.X)

    slices = packed.slices
    if slices is None:
        slices = cut(cut_slices)
        _metrics.inc("repro_f64_slice_packs_total")
    if _wants_pair(packed, backend):
        slices = slices._replace(pair=cut(cut_pair_slices))
        _metrics.inc("repro_f64_pair_slice_packs_total")
    out = dataclasses.replace(packed, slices=slices)
    entry = _PACK_CACHE.get(key)
    if entry is not None and entry[1] is packed:
        _PACK_CACHE[key] = (entry[0], out)
    return out


def _stack_on_pods(parts, dtype, mesh) -> PackedPartitions:
    """The pack with its institution axis sharded over ``mesh``'s pods.

    Pod p holds institutions ``p * k`` to ``p * k + k - 1`` of ``parts``
    in order, ``k = ceil(S / pods)``, each padded to the largest site as
    on one device; the last pods are filled up with empty sites (count
    0), whose summaries, and so whose shares, are zeros.  Each pod's
    rows are stacked on its own device, from where its parts are or are
    copied to, and the pods' stacks make one array.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..distributed.sharding import POD_AXIS

    devices = list(mesh.devices.flat)
    per = -(-len(parts) // len(devices))
    d = parts[0][0].shape[1]
    n_max = max(Xj.shape[0] for Xj, _ in parts)
    empty = (np.zeros((0, d), dtype), np.zeros((0,), np.float64))
    counts = np.zeros((len(devices) * per,), np.int32)
    counts[:len(parts)] = [Xj.shape[0] for Xj, _ in parts]
    Xs, ys = [], []
    for p, dev in enumerate(devices):
        mine = list(parts[p * per:(p + 1) * per])
        mine = jax.device_put(mine + [empty] * (per - len(mine)), dev)
        Xp, yp = _stack_pad([Xj for Xj, _ in mine], [yj for _, yj in mine],
                            n_max, jnp.dtype(dtype).name)
        Xs.append(Xp)
        ys.append(yp)
    sharding = NamedSharding(mesh, P(POD_AXIS))

    def one_array(blocks):
        return jax.make_array_from_single_device_arrays(
            (len(devices) * per,) + blocks[0].shape[1:], sharding, blocks)

    X = one_array(Xs)
    X32 = X if X.dtype == jnp.float32 else _to_f32(X)
    return PackedPartitions(X, X32, one_array(ys),
                            jax.device_put(counts, sharding))


def pack_partitions(
    parts: Sequence[tuple[jnp.ndarray, jnp.ndarray]],
    dtype=jnp.float64,
    backend: str | None = None,
    mesh=None,
) -> PackedPartitions:
    """Stack S ragged (X_j, y_j) partitions into one masked batch.

    Once per *study* — repeated calls with the same part arrays return
    the cached pack (a small LRU, so alternating studies or churned
    cohorts each keep their pack resident).  The padded copies (plus the
    f32 MXU operand) replace S live partition references, traded for a
    loop-free iteration.  Pad/stack run as one jitted graph and the f32
    cast as a second (a few hundred MB of pure memory movement at
    benchmark scale; doing it eagerly per part costs 2-3x that).  ``dtype`` is the X payload:
    float64 keeps the exact oracle payload (plus a separate f32 MXU
    operand); float32 stores one f32 buffer total — the TPU layout.
    ``backend`` names the summaries rung that will read the pack: for
    the compiled ``pallas`` and ``reference`` rungs with a float64
    payload the pack also carries the slices of X, and for the
    ``reference`` rung those of its pair design, cut on first use and
    kept with the cached pack.  ``mesh``: a pod mesh to shard the
    institution axis over (``_stack_on_pods``); the pack is cached
    under the mesh too.
    """
    if not parts:
        raise ValueError("need at least one partition")
    d = parts[0][0].shape[1]
    if any(Xj.shape[1] != d for Xj, _ in parts):
        raise ValueError("all partitions must share the feature dimension")
    # identity-keyed caching is only sound for immutable buffers: numpy
    # (or other mutable) inputs bypass the cache entirely
    cacheable = all(
        isinstance(Xj, jax.Array) and isinstance(yj, jax.Array)
        for Xj, yj in parts
    )
    key = _pack_cache_key(parts, dtype)
    if mesh is not None:
        key += (mesh,)
    if cacheable:
        hit = _PACK_CACHE.get(key)
        if hit is not None:
            _PACK_CACHE.move_to_end(key)
            if _wants_slices(hit[1], backend):
                return _with_slices(hit[1], key, backend, mesh)
            return hit[1]
    if mesh is not None:
        packed = _stack_on_pods(parts, dtype, mesh)
    else:
        counts = np.asarray([Xj.shape[0] for Xj in (p[0] for p in parts)],
                            np.int32)
        n_max = int(counts.max())
        Xs, ys = _stack_pad(
            [p[0] for p in parts], [p[1] for p in parts], n_max,
            jnp.dtype(dtype).name,
        )
        X32 = Xs if Xs.dtype == jnp.float32 else _to_f32(Xs)
        packed = PackedPartitions(Xs, X32, ys, jnp.asarray(counts))
    if cacheable:
        # evict-on-collect: if ANY part buffer dies, the ids in `key` may
        # be recycled, so the entry must go before a lookup can alias it
        evict = lambda _ref, key=key: _PACK_CACHE.pop(key, None)
        refs = [weakref.ref(b, evict) for part in parts for b in part]
        _PACK_CACHE[key] = (refs, packed)
        while len(_PACK_CACHE) > _PACK_CACHE_SIZE:
            _PACK_CACHE.popitem(last=False)
    if _wants_slices(packed, backend):
        return _with_slices(packed, key, backend, mesh)
    return packed


def _masked_irls_terms(beta, X, y, counts):
    """Shared payload-dtype IRLS terms: row mask, weights, gradient,
    deviance.  Single source of truth for every non-kernel backend —
    the "g/dev identical to the reference oracle" contract of the mixed
    backend holds by construction, not by keeping copies in sync."""
    with jax.named_scope("f64_terms"):
        n = X.shape[1]
        mask = (jnp.arange(n)[None, :] < counts[:, None]).astype(X.dtype)
        z = jnp.einsum("snd,d->sn", X, beta.astype(X.dtype))
        p = jax.nn.sigmoid(z)
        w = p * (1.0 - p) * mask
        g = jnp.einsum("snd,sn->sd", X, (y - p) * mask)
        dev = -2.0 * jnp.sum((y * z - jnp.logaddexp(0.0, z)) * mask, axis=1)
        return w, g, dev


def _reference_summaries(beta, X, y, counts, slices=None):
    """Masked batched oracle in the payload dtype (f64).  Given the
    pack's ``slices``, the float64 terms come from them, and the Gram
    from the slices of the pair design where the pack carries them:
    no float64 contraction over X then remains for a TPU to emulate."""
    if slices is None:
        w, g, dev = _masked_irls_terms(beta, X, y, counts)
    else:
        w, g, dev = sliced_terms(beta, slices, y, counts, jnp.float64)
    with jax.named_scope("gram"):
        if slices is None or slices.pair is None:
            H = jnp.einsum("sni,snj->sij", X * w[..., None], X)
        else:
            H = sliced_gram(w, slices.pair, X.shape[2])
    return H, g, dev


# Gram chunk length for the mixed backend: long enough that the f32 gemms
# stay MXU/SIMD-efficient, short enough that in-chunk f32 accumulation
# error stays below the f32 *operand* rounding floor (which chunking
# cannot remove).
MIXED_GRAM_CHUNK = 1024


def _mixed_summaries(beta, X, X32, y, counts, chunk: int = MIXED_GRAM_CHUNK):
    """f64 gradient/deviance + split-accumulation f32 Gram.

    The middle rung of the summaries precision ladder, between the f64
    reference (exact, but the f64 Gram IS the round's flop wall) and the
    f32-Gram kernel (fastest, largest H error):

    * z, p, w, g, dev — f64, identical to the reference oracle (the
      gradient fixes the Newton fixed point, so it must stay exact).
    * H — the two-pass "split" accumulation the TPU kernel would use at
      large N: f32 gemms over ``chunk``-row slabs of the weighted
      operand, merged across slabs in f64.  The f32 accumulation chain
      shrinks from N to ``chunk``, cutting the measured H error ~4.4x
      under the single-pass f32 Gram at N=2e5 (down to the f32 operand-
      rounding floor, ~1e-7 relative) at f32-gemm speed.

    Contract note: like the pallas backend, this holds CONVERGED-beta
    parity with the f64 oracle inside fixed-point quantization; it does
    NOT hold per-ROUND parity at production N (the mid-run Newton
    transient amplifies even the operand-floor H perturbation past the
    quantization tolerance) — use the reference backend for that.
    """
    n, d = X.shape[1], X.shape[2]
    w, g, dev = _masked_irls_terms(beta, X, y, counts)
    num_chunks = -(-n // chunk)
    pad = num_chunks * chunk - n

    def slabs(a):
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        return a.reshape(a.shape[0], num_chunks, chunk, d)

    with jax.named_scope("gram"):
        Xw32 = (X * w[..., None]).astype(jnp.float32)
        # (S, nc, d, d) f32 partial Grams, merged across slabs in f64
        Hc = jax.lax.dot_general(
            slabs(Xw32), slabs(X32), (((2,), (2,)), ((0, 1), (0, 1)))
        )
        H = jnp.sum(Hc.astype(jnp.float64), axis=1)
    return H, g, dev


# -- cross-validated summaries: fold masks over the SAME packed batch --------

class CVSummaries(NamedTuple):
    """Per-(config, institution) train summaries + held-out metrics.

    The selection subsystem's batched mirror of ``LocalSummaries``: every
    field carries leading (C, S) axes — C path configs (lambda x fold
    pairs, plus optional full-data fits with ``fold == -1``) over S
    institutions — all emitted by ONE pass over the packed batch.  The
    validation fields are per-institution secrets exactly like H/g/dev:
    they only ever leave an institution secret-shared.
    """

    hessian: jnp.ndarray  # (C, S, d, d) train-fold Gram
    gradient: jnp.ndarray  # (C, S, d) train-fold score
    deviance: jnp.ndarray  # (C, S) train-fold -2 log L
    count: jnp.ndarray  # (C, S) train-fold row count
    val_deviance: jnp.ndarray  # (C, S) held-out -2 log L
    val_correct: jnp.ndarray  # (C, S) held-out correct predictions
    val_count: jnp.ndarray  # (C, S) held-out row count


def _cv_masks(X, counts, fold_ids, fold_of):
    """(tmask, vmask) float64 (C, S, N): fold masks composed onto the
    ragged row mask.  ``fold_of == -1`` selects no validation rows, so a
    full-data fit shares the batch with the fold fits."""
    n = X.shape[1]
    row_ok = jnp.arange(n, dtype=jnp.int32)[None, :] < counts[:, None]
    on_fold = fold_ids[None] == fold_of[:, None, None]
    tmask = (row_ok[None] & ~on_fold).astype(jnp.float64)
    vmask = (row_ok[None] & on_fold).astype(jnp.float64)
    return tmask, vmask


def _cv_common_terms(betas, X, y, tmask, vmask):
    """f64 z/g/dev/val terms shared by the reference and mixed rungs (and
    matching the sim's f64-accumulation contract).  Returns everything
    except the Gram, which is what the rungs differ on."""
    with jax.named_scope("f64_terms"):
        s_dim = X.shape[0]
        z = jnp.einsum("snd,cd->csn", X, betas.astype(X.dtype))
        z = z.astype(jnp.float64)
        p = jax.nn.sigmoid(z)
        ll = y[None] * z - jnp.logaddexp(0.0, z)
        dev_tr = -2.0 * jnp.sum(ll * tmask, axis=2)
        dev_va = -2.0 * jnp.sum(ll * vmask, axis=2)
        acc_va = jnp.sum(
            jnp.where((z > 0.0) == (y[None] > 0.5), vmask, 0.0), axis=2
        )
        w = (p * (1.0 - p)) * tmask  # (C, S, N) train-fold IRLS weights
        resid = (y[None] - p) * tmask
        g = jnp.stack([
            jax.lax.dot_general(
                resid[:, s], X[s], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float64,
            )
            for s in range(s_dim)
        ], axis=1)  # (C, S, d)
        return w, g, dev_tr, dev_va, acc_va


def batched_cv_summaries(
    betas: jnp.ndarray,
    packed: PackedPartitions,
    fold_ids: jnp.ndarray,
    fold_of: jnp.ndarray,
    backend: str = "pallas",
    block_n: int = 512,
) -> CVSummaries:
    """All (config, institution) train summaries + held-out metrics in one
    launch over the packed batch — no per-fold repacking, ever.

    ``betas`` (C, d) holds one Newton iterate per path config;
    ``fold_ids`` (S, N_max) the per-row fold assignment (padding rows may
    hold anything — the row mask already excludes them); ``fold_of`` (C,)
    names each config's held-out fold (-1: none).  ``backend`` selects
    the same precision ladder as ``batched_local_summaries``:

    * "reference" — f64 end to end (per-round-parity rung),
    * "pallas"    — the kernel layout: f32 Gram, f64 g/dev
      (the CPU runs the XLA simulation, exactly like the non-CV path),
    * "mixed"     — f64 g/dev + chunked split-accumulation f32 Gram.

    The Gram on every rung runs as a ``lax.map`` over the config axis so
    the traced graph size is independent of path length.
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}")
    with jax.named_scope("summaries"):
        fold_ids = fold_ids.astype(jnp.int32)
        fold_of = fold_of.astype(jnp.int32)
        if backend == "pallas":
            from ..kernels import ops

            H, g, dev_tr, dev_va, acc_va, n_va = ops.fused_irls_cv(
                betas, packed.X, packed.y, fold_ids, fold_of,
                counts=packed.counts, block_n=block_n,
                mxu_operand=packed.X32,
            )
            # train + held-out rows partition the valid rows exactly (also
            # for fold_of == -1, where n_va == 0), so n_tr needs no dense
            # (C, S, N) mask materialization inside the sweep scan
            n_va = n_va.astype(jnp.float64)
            n_tr = packed.counts[None, :].astype(jnp.float64) - n_va
            return CVSummaries(
                H.astype(jnp.float64), g.astype(jnp.float64),
                dev_tr.astype(jnp.float64), n_tr,
                dev_va.astype(jnp.float64), acc_va.astype(jnp.float64),
                n_va,
            )
        X, y = packed.X, packed.y
        tmask, vmask = _cv_masks(X, packed.counts, fold_ids, fold_of)
        w, g, dev_tr, dev_va, acc_va = _cv_common_terms(
            betas, X, y, tmask, vmask
        )
        s_dim, d = X.shape[0], X.shape[2]
        if backend == "reference":
            def gram_one(w_c):  # (S, N) f64 -> (S, d, d) f64
                return jnp.stack([
                    (X[s] * w_c[s][:, None]).T @ X[s] for s in range(s_dim)
                ])

            with jax.named_scope("gram"):
                H = jax.lax.map(gram_one, w)
        else:  # mixed: chunked f32 gemms merged in f64, per config
            X32 = packed.X32
            n = X.shape[1]
            chunk = MIXED_GRAM_CHUNK
            num_chunks = -(-n // chunk)
            pad = num_chunks * chunk - n

            def slabs(a):
                a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                return a.reshape(s_dim, num_chunks, chunk, d)

            def gram_one(w_c):  # (S, N) -> (S, d, d): split accumulation
                Xw32 = slabs((X * w_c[..., None]).astype(jnp.float32))
                Hc = jax.lax.dot_general(
                    Xw32, X32s, (((2,), (2,)), ((0, 1), (0, 1)))
                )  # (S, nc, d, d) f32 partial Grams
                return jnp.sum(Hc.astype(jnp.float64), axis=1)

            with jax.named_scope("gram"):
                X32s = slabs(X32)
                H = jax.lax.map(gram_one, w)
        n_tr = jnp.sum(tmask, axis=2)
        n_va = jnp.sum(vmask, axis=2)
        return CVSummaries(H, g, dev_tr, n_tr, dev_va, acc_va, n_va)


def batched_local_summaries(
    beta: jnp.ndarray,
    packed: PackedPartitions,
    backend: str = "pallas",
    block_n: int = 512,
) -> LocalSummaries:
    """All S institutions' summaries in one launch.

    Returns a ``LocalSummaries`` whose fields carry a leading S axis:
    hessian (S, d, d), gradient (S, d), deviance (S,), count (S,) — the
    batched mirror of ``local_summaries`` (which remains the
    per-institution oracle).  Everything is traceable, so this composes
    into the jit-resident secure iteration.
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}")
    with jax.named_scope("summaries"):
        if backend == "mixed":
            H, g, dev = _mixed_summaries(
                beta, packed.X, packed.X32, packed.y, packed.counts
            )
            return LocalSummaries(H, g, dev, packed.counts)
        if backend == "pallas":
            from ..kernels import ops

            # the CPU runs the kernel's XLA simulation inside ops.fused_irls
            # (block_n then has no effect); a TPU runs the compiled blocked
            # kernel with VMEM-sized N tiles.
            H, g, dev = ops.fused_irls(
                beta, packed.X, packed.y, packed.counts,
                block_n=block_n, mxu_operand=packed.X32,
                slices=packed.slices,
            )
            # protocol dtype: the fixed-point encode needs f64 past 2**24
            H = H.astype(jnp.float64)
            g = g.astype(jnp.float64)
            dev = dev.astype(jnp.float64)
        else:
            H, g, dev = _reference_summaries(
                beta, packed.X, packed.y, packed.counts, packed.slices
            )
        return LocalSummaries(H, g, dev, packed.counts)
