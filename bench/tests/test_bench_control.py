"""``correct`` refuses the control and the program's faults.

A small copy of each cell (``conftest.TINY``) runs through the whole
harness, past its look for a chip, with the committed limits:

* a sound run is correct;
* the controls, the plain reference put in the program's place with
  every term one precision rung lower, or with only the Gram lower, are
  not;
* nor is the program with a fault planted under the timed path: a Newton
  step that returns its state unchanged; half of each site's rows left
  out and the summaries scaled up from the rest; an answer altered where
  the coordinator returns it.  (The cells run on one chip: there is no
  exchange between chips to leave out.)
"""
import dataclasses

import jax
import numpy as np
import pytest

from bench import reference, run

WORKLOADS = ["d128-fit", "synthetic-fit", "d128-cvpath"]


@pytest.fixture(autouse=True)
def fresh_programs(no_chip):
    """Programs traced under a planted fault must not outlive the test."""
    import repro.core as core

    jax.clear_caches()
    core.pack_cache_clear()
    yield
    jax.clear_caches()
    core.pack_cache_clear()


def _run(root, workload):
    return run.run(workload, 2**32 + 7, 0.5, False, root=root)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_sound_run_is_correct(tiny_root, workload):
    res = _run(tiny_root, workload)
    assert res["correct"], res["checks"]


# every term one rung lower in every cell; the Gram alone where the
# configuration's Gram is float32, the step a kernel change would take
CONTROLS = [(w, "lower") for w in WORKLOADS] + [("d128-fit", "gram")]


@pytest.mark.parametrize("workload,control", CONTROLS)
def test_the_control_is_not(tiny_root, workload, control, monkeypatch):
    import importlib

    _, _, config, traffic, _ = run.load_cell(workload, tiny_root)
    kind = importlib.import_module(f"bench.kinds.{traffic['kind']}")
    prec = reference.CONTROLS[control](config["precision"])

    def in_place(self, job):
        if traffic["kind"] == "cv_path":
            return self.control_answers(job, self.parts, prec)
        return self.control_answers([job], self.parts, prec)[0]

    monkeypatch.setattr(kind.Cell, "run_job", in_place)
    res = _run(tiny_root, workload)
    assert not res["correct"], res["checks"]


def _unchanged_state(monkeypatch):
    import repro.core.newton as newton
    import repro.selection.path as path

    monkeypatch.setattr(newton, "prox_newton_step",
                        lambda beta, H, g, lam, l1, **kw: beta)
    monkeypatch.setattr(path, "_batched_update",
                        lambda betas, H, g, lams, l1: betas)


def _half_the_batch(monkeypatch):
    import repro.core.scanfit as scanfit
    import repro.selection.path as path

    whole = scanfit.batched_local_summaries

    def half(beta, packed, backend="pallas", block_n=512):
        kept = dataclasses.replace(packed, counts=packed.counts // 2)
        s = whole(beta, kept, backend=backend, block_n=block_n)
        return s._replace(hessian=2.0 * s.hessian,
                          gradient=2.0 * s.gradient,
                          deviance=2.0 * s.deviance)

    whole_cv = path.batched_cv_summaries

    def half_cv(betas, packed, fold_ids, fold_of, backend="pallas",
                block_n=512):
        kept = dataclasses.replace(packed, counts=packed.counts // 2)
        s = whole_cv(betas, kept, fold_ids, fold_of, backend=backend,
                     block_n=block_n)
        return type(s)(*(2.0 * v for v in s))

    monkeypatch.setattr(scanfit, "batched_local_summaries", half)
    monkeypatch.setattr(path, "batched_cv_summaries", half_cv)


def _altered_answer(monkeypatch):
    from repro.core.protocol import StudyCoordinator
    from repro.selection import SelectionCoordinator

    honest = StudyCoordinator.run

    def altered(self, max_iter=50):
        beta = np.array(honest(self, max_iter))
        beta[0] += 1e-6 * np.max(np.abs(beta))
        return beta

    honest_path = SelectionCoordinator.run_path

    def altered_path(self):
        rep = honest_path(self)
        rep.beta[0] += 1e-6 * np.max(np.abs(rep.beta))
        return rep

    monkeypatch.setattr(StudyCoordinator, "run", altered)
    monkeypatch.setattr(SelectionCoordinator, "run_path", altered_path)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_batch,
                                   _altered_answer])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_fault_is_not(tiny_root, workload, fault, monkeypatch):
    fault(monkeypatch)
    res = _run(tiny_root, workload)
    assert not res["correct"], res["checks"]
