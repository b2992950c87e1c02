"""Device time by the program's named scopes, and the idle gaps of the
coordinator's spans, on a small recorded trace
(bench/tests/data/scoped_trace.json: the ops and spans of two jobs, and
the HLO text of the programs they ran)."""
import importlib
import json
import pathlib
import types

import pytest

from bench import scopes, trace

DATA = pathlib.Path(__file__).parent / "data"
NEW = ["summaries_ms_per_round", "f64_terms_ms_per_round",
       "secure_round_ms_per_round", "newton_solve_ms_per_round",
       "unscoped_ms_per_round"]


@pytest.fixture
def recorded():
    raw = json.loads((DATA / "scoped_trace.json").read_text())
    return trace.reduce(trace.Trace.from_json(json.dumps(raw))), raw["hlo"]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/while/body/summaries/jit(fused_irls_sim)/f64_terms/mul",
     "summaries/f64_terms"),
    ("jit(f)/summaries/gram/jit(fused_irls_pallas)/pallas_call",
     "summaries/gram"),
    ("jit(f)/summaries/operands/jit(_pad)/pad", "summaries/operands"),
    ("jit(f)/summaries/convert_element_type", "summaries"),
    ("jit(f)/protect/jit(_protect_flat)/threefry2x32", "protect"),
    ("jit(f)/newton_solve/jit(_cholesky)/cholesky", "newton_solve"),
    ("jit(f)/gram/mul", ""),  # a part of summaries outside it
    ("jit(f)/while/body/jit(_where)/select_n", ""),
])
def test_the_innermost_scope_wins(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_scope_seconds_split_the_leaf_time(recorded):
    summary, hlo = recorded
    secs = scopes.scope_seconds(summary, hlo)
    # the while op holds the round's ops and is not counted; fusion.1
    # is named in two programs, and its result type picks the fit's
    assert secs == pytest.approx({
        "summaries/f64_terms": 800e-9, "summaries/gram": 300e-9,
        "summaries": 50e-9, "protect": 200e-9, "reveal": 100e-9,
        "newton_solve": 100e-9, "": 100e-9})
    assert sum(secs.values()) == pytest.approx(sum(summary.op_s.values()))
    assert sum(secs.values()) == pytest.approx(summary.busy_s)


def test_a_program_without_scopes_gives_none(recorded):
    summary, _ = recorded
    assert scopes.scope_seconds(summary, ["HloModule m\n"]) is None
    small = trace.reduce(trace.Trace.from_json(
        (DATA / "small_trace.json").read_text()))
    assert scopes.scope_seconds(small, recorded[1]) is None


@pytest.mark.parametrize("op, secs, split", [
    # an instruction no program holds: tolerated below MAX_UNMAPPED
    ("%fusion.77 = f32[2]{0} fusion(f32[2]{0} %p)", 5e-9, True),
    ("%fusion.77 = f32[2]{0} fusion(f32[2]{0} %p)", 50e-9, False),
    # named in both programs, with the type of neither
    ("%fusion.1 = f16[4]{0} fusion(f16[4]{0} %p)", 50e-9, False),
])
def test_unmapped_time_beyond_a_sliver_gives_none(recorded, op, secs,
                                                  split, capsys):
    summary, hlo = recorded
    summary.op_s[op] = secs  # of 1,650 ns of leaf time before
    got = scopes.scope_seconds(summary, hlo)
    assert (got is not None) == split
    if split:
        assert sum(got.values()) == pytest.approx(1650e-9)
    share = secs / (1650e-9 + secs)
    assert f"{share:.3%} of the leaf-op time unmapped" in (
        capsys.readouterr().err)


def test_idle_gaps_go_to_the_inner_coordinator_span(recorded):
    summary, _ = recorded
    # [0, 1000) while dispatching, [2200, 6000) by its middle in the
    # round reports, [6450, 10000) in the second job's readback, which
    # lies inside its step_block
    assert summary.gaps == [
        ("StudyCoordinator.reports", pytest.approx(3800e-9)),
        ("StudyCoordinator.readback", pytest.approx(3550e-9)),
        ("StudyCoordinator.dispatch", pytest.approx(1000e-9))]


def _ctx(summary, rounds=(1, 1)):
    return types.SimpleNamespace(
        trace=summary, traced=[types.SimpleNamespace(rounds=r)
                               for r in rounds])


def test_each_reader(recorded, monkeypatch):
    summary, hlo = recorded
    monkeypatch.setattr(scopes, "live_hlo_texts", lambda: hlo)
    got = {name: importlib.import_module(f"bench.metrics.{name}").read(
        _ctx(summary)) for name in NEW}
    # two rounds in the window
    assert got == pytest.approx({
        "summaries_ms_per_round": 1150e-9 * 1e3 / 2,
        "f64_terms_ms_per_round": 800e-9 * 1e3 / 2,
        "secure_round_ms_per_round": 300e-9 * 1e3 / 2,
        "newton_solve_ms_per_round": 100e-9 * 1e3 / 2,
        "unscoped_ms_per_round": 100e-9 * 1e3 / 2})
    parts = ("summaries", "secure_round", "newton_solve", "unscoped")
    assert sum(got[f"{p}_ms_per_round"] for p in parts) == pytest.approx(
        summary.busy_s * 1e3 / 2)


@pytest.mark.parametrize("name", NEW)
def test_each_reader_is_silent_without_a_trace(name, recorded, monkeypatch):
    reader = importlib.import_module(f"bench.metrics.{name}")
    assert reader.read(_ctx(None)) is None
    # nor where the program names none of it
    small = trace.reduce(trace.Trace.from_json(
        (DATA / "small_trace.json").read_text()))
    monkeypatch.setattr(scopes, "live_hlo_texts", lambda: recorded[1])
    assert reader.read(_ctx(small)) is None


def test_live_programs_name_the_scopes():
    """On the CPU: a fit's compiled program, found among the process's
    live executables, maps its own instructions to their scopes."""
    import jax

    from repro.core import Institution, SecureCollective, StudyCoordinator
    from repro.data import generate_synthetic

    study = generate_synthetic(jax.random.PRNGKey(5), num_institutions=2,
                               records_per_institution=30, dim=3)
    StudyCoordinator(
        [Institution(f"s{j}", X, y) for j, (X, y) in enumerate(study.parts)],
        lam=1.0, protect="both", aggregator=SecureCollective(
            backend="pallas"), fused=True, rounds="scan",
        summaries_backend="pallas").run(max_iter=3)
    texts = scopes.live_hlo_texts()
    (fit,) = [t for t in texts
              if t.startswith("HloModule jit_fit_scan_block")
              and "f64[2,30,3]" in t]
    index = scopes.hlo_index([fit])
    # a trace op named after one of its instructions gets that scope
    every = scopes.hlo_index(texts)
    name, text = next((k, v[0][0]) for k, v in index.items()
                      if {s for _, s in every[k]} == {"newton_solve"})
    summary = trace.Summary(1.0, 1.0, {f"%{name} = {text}"[:160]: 0.5},
                            [], [], 1)
    assert scopes.scope_seconds(summary) == {"newton_solve": 0.5}
