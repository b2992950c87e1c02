"""Observability layer: tracer, ledger, metrics, purity lint, audit.

The tentpole invariants:

* disabled tracing is invisible (no spans, bit-identical fits);
* the span exporter round-trips (JSONL);
* a coordinator's scan fit records its host phases as spans inside
  ``step_block`` and ``run``;
* the privacy ledger counts every host-wrapper invocation of a
  declassification boundary, and the audit reconciles those counts
  against the static gate's certified jaxpr census — with the
  deliberate extra-reveal fixture FLAGGED;
* the obs core stays stdlib-only (purity lint), so none of the above
  can ever introduce a device dependency or hidden sync.
"""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.drivers import all_driver_specs
from repro.analysis.lints import lint_obs_purity
from repro.core.secure_agg import SecureAggregator
from repro.data import generate_synthetic
from repro.obs import audit, ledger, metrics, trace


@pytest.fixture(autouse=True)
def _obs_clean():
    trace.disable()
    ledger.disable()
    ledger.reset()
    metrics.reset()
    yield
    trace.disable()
    ledger.disable()
    ledger.reset()
    metrics.reset()


@pytest.fixture(scope="module")
def study():
    return generate_synthetic(
        jax.random.PRNGKey(7), num_institutions=3,
        records_per_institution=100, dim=5,
    )


# ------------------------------------------------------------- span tracer

def test_disabled_tracing_records_nothing():
    assert trace.get() is None
    with trace.span("protect", "x", foo=1) as s:
        s.set(bar=2)  # the noop span accepts the live-span API
    assert trace.get() is None


def test_spans_record_and_summarize():
    tracer = trace.enable(capacity=16)
    with trace.span("protect", "p1", rows=8):
        pass
    with trace.span("reveal"):
        pass
    assert [s.kind for s in tracer.spans] == ["protect", "reveal"]
    s = tracer.spans[0]
    assert s.name == "p1" and s.attrs == {"rows": 8} and s.duration >= 0
    summary = tracer.summary()
    assert summary["protect"]["count"] == 1
    assert len(tracer.summary_lines()) == 3  # header + 2 kinds


def test_ring_buffer_evicts_oldest():
    tracer = trace.enable(capacity=3)
    for i in range(5):
        with trace.span("k", f"s{i}"):
            pass
    assert [s.name for s in tracer.spans] == ["s2", "s3", "s4"]


def test_traced_decorator_labels_qualname():
    tracer = trace.enable()

    @trace.traced("newton")
    def my_step():
        return 42

    assert my_step() == 42
    assert tracer.spans[0].name.endswith("my_step")
    trace.disable()
    assert my_step() == 42  # disabled path: plain call-through


def test_jsonl_roundtrip_and_chrome_trace(tmp_path):
    tracer = trace.enable()
    with trace.span("protect", "p", rows=8):
        with trace.span("reveal", "r"):
            pass
    tracer = trace.disable()
    n = tracer.export_jsonl(tmp_path / "run.jsonl")
    assert n == 2

    back = trace.SpanTracer()
    with open(tmp_path / "run.jsonl") as fh:
        for line in fh:
            back.record(json.loads(line))
    assert back.summary() == tracer.summary()
    by_name = {s.name: s for s in back.spans}
    # the reveal span nests inside the protect span on the timeline
    assert by_name["p"].t0 <= by_name["r"].t0 <= by_name["r"].t1 \
        <= by_name["p"].t1
    assert by_name["p"].attrs == {"rows": 8}


def test_driver_emits_spans(study):
    from repro.core.newton import SecureFitDriver

    tracer = trace.enable()
    SecureFitDriver(study.parts, lam=1.0, protect="gradient",
                    fused=False).run(max_iter=3)
    kinds = {s.kind for s in tracer.spans}
    assert {"newton", "protect", "aggregate", "reveal"} <= kinds


COORDINATOR_SPANS = ["StudyCoordinator.__init__", "StudyCoordinator.cohort",
                     "StudyCoordinator.pack", "StudyCoordinator.dispatch",
                     "StudyCoordinator.readback", "StudyCoordinator.reports",
                     "StudyCoordinator.result"]


def test_coordinator_phases_are_spans_inside_its_fit(study):
    from repro.core import Institution, SecureCollective, StudyCoordinator

    sites = [Institution(f"s{j}", X, y)
             for j, (X, y) in enumerate(study.parts)]
    tracer = trace.enable()
    try:
        coord = StudyCoordinator(
            sites, lam=1.0, protect="both",
            aggregator=SecureCollective(backend="pallas"), fused=True,
            rounds="scan", rounds_per_sync=2)
        coord.run(max_iter=6)
    finally:
        trace.disable()
    spans = list(tracer.spans)
    assert set(COORDINATOR_SPANS) <= {s.name for s in spans}
    assert {s.kind for s in spans if s.name in COORDINATOR_SPANS} == {
        "coordinator"}
    blocks = [s for s in spans if s.name == "StudyCoordinator.step_block"]
    assert len(blocks) == -(-coord.iteration // 2) >= 2
    for phase in ("cohort", "pack", "dispatch", "readback", "reports"):
        inner = [s for s in spans if s.name == f"StudyCoordinator.{phase}"]
        assert len(inner) == len(blocks)
        for s, b in zip(inner, blocks):
            assert b.t0 <= s.t0 <= s.t1 <= b.t1
    (init,) = [s for s in spans if s.name == "StudyCoordinator.__init__"]
    (result,) = [s for s in spans if s.name == "StudyCoordinator.result"]
    assert init.t1 <= blocks[0].t0 and blocks[-1].t1 <= result.t0


def test_tracing_is_bit_invisible(study):
    from repro.core.newton import SecureFitDriver

    def fit():
        d = SecureFitDriver(study.parts, lam=1.0, protect="gradient",
                            aggregator=SecureAggregator(backend="pallas"),
                            fused=True)
        d.run(max_iter=6)
        return np.asarray(d.beta)

    def coordinator_fit():
        from repro.core import Institution, StudyCoordinator

        sites = [Institution(f"s{j}", X, y)
                 for j, (X, y) in enumerate(study.parts)]
        c = StudyCoordinator(sites, lam=1.0, protect="both",
                             aggregator=SecureAggregator(backend="pallas"),
                             fused=True, rounds="scan", rounds_per_sync=2)
        beta = c.run(max_iter=6)
        return beta, np.asarray(c.trace), c.iteration

    off, coord_off = fit(), coordinator_fit()
    tracer = trace.enable(profiler=True)  # spans as profiler annotations
    on, coord_on = fit(), coordinator_fit()
    trace.disable()
    np.testing.assert_array_equal(off, on)
    # the coordinator's phase spans fired and changed none of its answers
    assert "StudyCoordinator.readback" in {s.name for s in tracer.spans}
    np.testing.assert_array_equal(coord_off[0], coord_on[0])
    np.testing.assert_array_equal(coord_off[1], coord_on[1])
    assert coord_off[2] == coord_on[2]


# ---------------------------------------------------------- privacy ledger

def test_ledger_disabled_records_nothing():
    ledger.record_site("_reveal_flat", what="x", shape=(2, 2))
    assert ledger.counts() == {}


def test_ledger_capture_counts_wrapper_invocations():
    agg = SecureAggregator(backend="pallas")
    tree = {"g": jnp.arange(4.0)}
    with ledger.capture() as cap:
        prot = agg.protect(jax.random.PRNGKey(0), tree)
        agg.reveal(agg.aggregate([prot, prot]))
    assert cap.by_site.get("_protect_flat") == 1
    assert cap.by_site.get("_reveal_flat") == 1
    # and captures reset: outside the capture the ledger is off again,
    # so further boundary invocations leave the totals untouched
    assert not ledger.enabled()
    before = ledger.counts()
    ledger.record_site("_reveal_flat")
    assert ledger.counts() == before


def test_ledger_counts_per_invocation_despite_jit_cache():
    agg = SecureAggregator(backend="pallas")
    tree = {"g": jnp.arange(4.0)}
    with ledger.capture() as cap:
        for i in range(3):  # same shapes: jit cache hits after the first
            agg.protect(jax.random.PRNGKey(i), tree)
    assert cap.by_site["_protect_flat"] == 3


def test_declassify_sum_records_shape():
    from repro.core.secure_agg import declassify_sum

    with ledger.capture() as cap:
        declassify_sum(jnp.ones((4, 3)), axis=0)
    (key,) = [k for k in cap.counts if k[0] == "declassify_sum"]
    assert key[2] == (4, 3)


# ----------------------------------------------------------------- metrics

def test_observe_round_and_prometheus_render():
    metrics.observe_round("secure_fit", 1024, objective=3.5,
                          grad_norm=0.25, step_norm=0.1)
    metrics.observe_round("secure_fit", 1024)
    assert metrics.get("repro_rounds_total", driver="secure_fit") == 2
    assert metrics.get("repro_bytes_total", driver="secure_fit") == 2048
    assert metrics.get("repro_grad_norm", driver="secure_fit") == 0.25
    text = metrics.render_prometheus(
        metrics.ledger_counter_series({"_reveal_flat": 2,
                                       "_protect_flat": 2})
    )
    assert 'repro_rounds_total{driver="secure_fit"} 2' in text
    assert 'repro_declass_total{site="_reveal_flat"} 2' in text
    assert "repro_protect_total 2" in text
    assert "# TYPE repro_objective gauge" in text


# --------------------------------------------------------- obs purity lint

def test_obs_purity_real_modules_clean():
    rep = lint_obs_purity()
    assert rep.ok, [f.format() for f in rep.errors()]
    assert len([f for f in rep.findings if f.severity == "info"]) == 3


def test_obs_purity_catches_jax_import_and_materializer():
    bad_import = "import jax\nX = 1\n"
    bad_sync = ("def f(x):\n"
                "    import math\n"
                "    return jax.device_get(x)\n")
    rep = lint_obs_purity(modules={"obs/fake.py": bad_import})
    assert not rep.ok and "import of 'jax'" in rep.errors()[0].message
    rep = lint_obs_purity(modules={"obs/fake.py": bad_sync})
    assert not rep.ok and "device_get" in rep.errors()[0].message


def test_obs_purity_allows_the_lazy_profiler_hook():
    src = ("class SpanTracer:\n"
           "    def _annotation(self, name):\n"
           "        import jax.profiler\n"
           "        return jax.profiler.TraceAnnotation(name)\n")
    rep = lint_obs_purity(modules={"obs/trace.py": src})
    assert rep.ok, [f.format() for f in rep.errors()]


# ------------------------------------------------------------ the audit

def _fused_spec():
    return next(s for s in all_driver_specs()
                if s.name == "secure_fit_fused[protect=gradient]")


def test_graph_census_finds_the_certified_boundaries():
    spec = _fused_spec()
    closed, _ = spec.build()
    census = audit.graph_census(closed)
    by_site = {}
    for (site, _shape), n in census.items():
        by_site[site] = by_site.get(site, 0) + n
    assert by_site == {"_protect_flat": 1, "_reveal_flat": 1,
                       "declassify_sum": 1}


def test_audit_spec_reconciles():
    res = audit.audit_spec(_fused_spec())
    assert not res.skipped
    assert res.ok, res.findings()
    assert res.recorded == res.expected != {}


def test_extra_reveal_is_flagged():
    res = audit.extra_reveal_fixture(_fused_spec())
    assert not res.ok
    assert any("UNCERTIFIED" in f for f in res.findings())


def test_audit_cli_subprocess(tmp_path):
    """The full CLI path: 8 host devices, JSON output, self-test armed.

    Subprocess on purpose — the psum specs need XLA_FLAGS applied before
    jax imports (banned in-process; see conftest).  Restricted to the
    fused drivers to keep the smoke fast; bench_smoke runs all 12.
    """
    import os
    import pathlib

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the CLI sets its own host-device flags
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs", "audit", "--json",
         "--drivers", "secure_fit_fused",
         "--textfile", str(tmp_path / "obs.prom")],
        capture_output=True, text=True, env=env,
        cwd=str(pathlib.Path(__file__).resolve().parents[1]),
    )
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["ok"]
    assert len(payload["specs"]) == 2
    assert all(s["ok"] and not s["skipped"] for s in payload["specs"])
    assert payload["fixture"] is not None and not payload["fixture"]["ok"]
    prom = (tmp_path / "obs.prom").read_text()
    assert 'repro_declass_total{site="_reveal_flat"}' in prom
