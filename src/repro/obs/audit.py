"""Runtime privacy audit: reconcile the ledger against the static gate.

The static gate (:mod:`repro.analysis`) certifies, per driver spec, a
closed jaxpr in which every declassification is a named jit boundary
(``_reveal_flat`` / ``_distributed_reveal`` / ``declassify_sum``).  The
runtime ledger (:mod:`repro.obs.ledger`) counts every Python-level
invocation of those boundaries.  This module closes the loop:

1. **Expected census** — walk the spec's certified jaxpr (recursing
   through scan/cond/jit/shard_map bodies) and count every boundary
   equation, keyed ``(site, operand shape)``.  One equation == one
   wrapper invocation during the trace, because the hooks live in the
   host wrappers outside the jitted bodies.
2. **Recorded counts** — ``jax.clear_caches()`` (so the runner's
   enclosing graphs re-trace rather than silently reusing a build-time
   cache entry), then execute the spec's runnable form under
   :func:`repro.obs.ledger.capture`.
3. **Reconcile** — the two multisets must be EQUAL.  Anything extra the
   process did (e.g. a host-level reveal of a per-institution buffer —
   see :func:`extra_reveal_fixture`) fires the wrapper hook regardless
   of jit-cache state and surfaces as a count mismatch: a finding.

Dispatches of an already-certified compiled graph record nothing and
need nothing: they cannot add declassification sites.  What the audit
certifies is therefore exactly: *every declassification this process
performed is an equation of a gate-certified graph (or an expected
host-level call), in the expected multiplicity.*

This module imports jax and must only be loaded behind the CLI
(``python -m repro.obs audit``) or tests — never from the obs core
modules the drivers import.
"""
from __future__ import annotations

import dataclasses
from collections import Counter

import jax

from . import ledger

__all__ = ["graph_census", "audit_spec", "extra_reveal_fixture",
           "run_audit", "AuditResult", "SpecAudit"]

# every boundary the census counts: the three declassification sites
# plus the protect direction (same wrapper mechanics, same invariant)
SITES = ledger.DECLASS_SITES + ("_protect_flat",)


def _sub_jaxprs(eqn):
    """Every jaxpr nested in an equation's params (scan/cond/jit/...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for v in eqn.params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, Jaxpr):
                    yield x


def _operand_shape(eqn) -> tuple:
    """The boundary's payload shape: its highest-rank array operand.

    Matches what the host wrapper records (``buf.shape`` — the share
    buffer for protect/reveal, the summed tensor for declassify_sum);
    scalar statics and rng keys rank below the payload buffer.
    """
    shapes = [tuple(v.aval.shape) for v in eqn.invars
              if hasattr(v, "aval") and hasattr(v.aval, "shape")]
    return max(shapes, key=len, default=())


def graph_census(closed) -> dict:
    """Count boundary equations in a certified jaxpr: (site, shape) -> n.

    A ``lax.scan`` body is counted ONCE regardless of trip count and
    both ``lax.cond`` branches are counted — mirroring exactly how often
    the host wrappers fire while the graph is traced.
    """
    counts: Counter = Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "jit" \
                    and eqn.params.get("name") in SITES:
                counts[(eqn.params["name"], _operand_shape(eqn))] += 1
                continue  # the boundary body holds no further boundaries
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(closed.jaxpr)
    return dict(counts)


def _recorded_census(cap: ledger.Capture) -> dict:
    """Fold captured ledger counts to the census key (site, shape)."""
    out: Counter = Counter()
    for (site, _what, shape, _thr), n in cap.counts.items():
        out[(site, tuple(shape))] += n
    return dict(out)


@dataclasses.dataclass
class SpecAudit:
    """One spec's reconciliation result."""

    name: str
    expected: dict  # (site, shape) -> n from the certified graph
    recorded: dict  # (site, shape) -> n from the runtime ledger
    skipped: str = ""  # non-empty: why the runner did not execute

    @property
    def ok(self) -> bool:
        return bool(self.skipped) or self.expected == self.recorded

    def findings(self) -> list[str]:
        if self.skipped:
            return []
        out = []
        keys = sorted(set(self.expected) | set(self.recorded))
        for key in keys:
            e = self.expected.get(key, 0)
            r = self.recorded.get(key, 0)
            if e != r:
                site, shape = key
                out.append(
                    f"{self.name}: {site}{list(shape)} executed {r}x, "
                    f"certified graph has {e} site(s) — "
                    + ("UNCERTIFIED declassification" if r > e
                       else "certified site never executed")
                )
        return out

    def by_site(self, which: dict) -> dict:
        folded: Counter = Counter()
        for (site, _shape), n in which.items():
            folded[site] += n
        return dict(folded)


def audit_spec(spec) -> SpecAudit:
    """Reconcile one DriverSpec: census of its graph vs a captured run."""
    closed, _taints = spec.build()
    expected = graph_census(closed)
    if spec.runner is None:
        return SpecAudit(spec.name, expected, {}, skipped="no runner")
    if jax.device_count() < getattr(spec, "min_devices", 1):
        return SpecAudit(
            spec.name, expected, {},
            skipped=f"needs {spec.min_devices} devices, "
                    f"have {jax.device_count()}",
        )
    # the build's make_jaxpr left this spec's enclosing graphs in the jit
    # cache; clear so the runner re-traces and the wrappers re-fire
    jax.clear_caches()
    with ledger.capture() as cap:
        spec.runner()
    return SpecAudit(spec.name, expected, _recorded_census(cap))


def extra_reveal_fixture(spec) -> SpecAudit:
    """A deliberately-leaky run the audit MUST flag (self-test).

    Executes the spec's certified round, then performs the classic
    coordinator attack: a host-level :func:`_reveal_flat` on a
    protected buffer that never went through Algorithm 2's
    institution-axis aggregation.  The host wrapper fires regardless of
    jit-cache state, so the recorded count exceeds the certified census
    and the audit reports an UNCERTIFIED declassification.
    """
    closed, _taints = spec.build()
    expected = graph_census(closed)
    jax.clear_caches()
    with ledger.capture() as cap:
        spec.runner()
        # ---- the attack: peek at one submission's share stack --------
        import jax.numpy as jnp

        from ..analysis.drivers import _aggregator
        from ..core.collective import _reveal_flat

        agg = _aggregator()
        prot = agg.protect(jax.random.PRNGKey(1),
                           {"gradient": jnp.arange(4.0)})
        t = agg.scheme.threshold
        _reveal_flat(prot.buf[:t], agg.scheme, agg.codec.frac_bits,
                     tuple(range(1, t + 1)))
    audit = SpecAudit(spec.name + "+extra_reveal", expected,
                      _recorded_census(cap))
    return audit


@dataclasses.dataclass
class AuditResult:
    """The whole audit: per-spec reconciliations + the leak self-test."""

    specs: list
    fixture: SpecAudit | None = None

    @property
    def ok(self) -> bool:
        clean = all(s.ok for s in self.specs)
        # the self-test must FAIL reconciliation, or the audit is blind
        armed = self.fixture is None or not self.fixture.ok
        return clean and armed

    def total_by_site(self) -> dict:
        folded: Counter = Counter()
        for s in self.specs:
            for (site, _shape), n in s.recorded.items():
                folded[site] += n
        return dict(folded)

    def lines(self) -> list[str]:
        out = []
        for s in self.specs:
            if s.skipped:
                out.append(f"SKIP  {s.name} ({s.skipped})")
                continue
            summary = " ".join(
                f"{site}={n}" for site, n in
                sorted(s.by_site(s.recorded).items())
            ) or "no boundaries"
            out.append(f"{'OK' if s.ok else 'MISMATCH'}    {s.name}  "
                       f"[{summary}]")
            out.extend(f"  [finding] {f}" for f in s.findings())
        if self.fixture is not None:
            if self.fixture.ok:
                out.append(
                    "BLIND   extra-reveal self-test was NOT flagged — "
                    "the runtime audit cannot see host-level reveals"
                )
            else:
                out.append(f"FLAGGED {self.fixture.name} "
                           "(the deliberate leak was caught)")
                out.extend(f"  [finding] {f}"
                           for f in self.fixture.findings())
        audited = sum(1 for s in self.specs if not s.skipped)
        skipped = len(self.specs) - audited
        out.append(
            f"audit: {'PASS' if self.ok else 'FAIL'} "
            f"({audited} drivers reconciled, {skipped} skipped)"
        )
        return out

    def to_dict(self) -> dict:
        def spec_dict(s):
            return {
                "name": s.name,
                "ok": s.ok,
                "skipped": s.skipped,
                "expected": {f"{site}{list(shape)}": n
                             for (site, shape), n in s.expected.items()},
                "recorded": {f"{site}{list(shape)}": n
                             for (site, shape), n in s.recorded.items()},
                "findings": s.findings(),
            }

        return {
            "ok": self.ok,
            "specs": [spec_dict(s) for s in self.specs],
            "fixture": (spec_dict(self.fixture)
                        if self.fixture is not None else None),
            "total_by_site": self.total_by_site(),
        }


def run_audit(drivers: list[str] | None = None,
              with_fixture: bool = True) -> AuditResult:
    """Audit every (matching) driver spec; arm the leak self-test."""
    from ..analysis.drivers import all_driver_specs

    specs = all_driver_specs()
    if drivers:
        specs = [s for s in specs
                 if any(pat in s.name for pat in drivers)]
    audits = [audit_spec(s) for s in specs]
    fixture = None
    if with_fixture:
        runnable = [s for s in specs
                    if s.runner is not None
                    and jax.device_count() >= getattr(s, "min_devices", 1)]
        if runnable:
            fixture = extra_reveal_fixture(runnable[0])
    return AuditResult(audits, fixture)
