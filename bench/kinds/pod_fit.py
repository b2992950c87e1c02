"""Traffic kind ``pod_fit``: ``single_fit``'s closed loop, with the sites
sharded over a pod mesh of the configuration's ``pods`` chips.

The sites are dealt to the pods round-robin by size rank (site ``j`` of
``data.site_sizes`` is the ``j``-th largest), so the pods carry like
rows, and each site's rows go onto its pod's chip.  Each job builds a
``StudyCoordinator(pods=...)``: every chip takes its own sites through
summaries, protect and the local share sum, the pods' share buffers meet
on the exact cross-chip wire, and reveal and solve run on every chip.
The pooled fit of ``bench/reference.py`` decides ``correct``, as in
``single_fit``: it does not depend on where the sites sit.
"""
from __future__ import annotations

import dataclasses
import inspect
import sys

import jax
import numpy as np

from .. import wire
from . import single_fit

__all__ = ["Answer", "Cell"]

WIRE_COUNTER = "repro_pod_wire_bytes_total"


@dataclasses.dataclass
class Answer(single_fit.Answer):
    """A fit's answer and the bytes its rounds put on the wire per chip
    (``None`` where the program keeps no such counter)."""

    wire_bytes: float | None = None


class Cell(single_fit.Cell):
    def setup(self) -> dict:
        core = single_fit._program()
        params = inspect.signature(core.StudyCoordinator).parameters
        if "pods" not in params:
            raise RuntimeError("the program cannot shard a fit over pods: "
                               "StudyCoordinator takes no 'pods'")
        pods = int(self.config["pods"])
        if jax.device_count() < pods:
            raise RuntimeError(f"the cell needs {pods} chips, JAX sees "
                               f"{jax.device_count()}")
        return super().setup()

    def warm(self):
        """The sites onto their pods' chips, then the warm job."""
        from repro.distributed.multihost import pod_mesh

        core = single_fit._program()
        pods = int(self.config["pods"])
        devices = list(pod_mesh(pods).devices.flat)
        placed = []
        for p, dev in enumerate(devices):
            for site in self.sites[p::pods]:
                X, y = jax.device_put((site.X, site.y), dev)
                placed.append(core.Institution(site.name, X, y))
        jax.block_until_ready([(s.X, s.y) for s in placed])
        self.sites = placed
        super().warm()

    def run_job(self, lam: float) -> Answer:
        from repro.obs import metrics

        core = single_fit._program()
        cfg = self.config
        before = metrics.get(WIRE_COUNTER)
        coord = core.StudyCoordinator(
            self.sites, lam=lam, protect=cfg["protect"], aggregator=self.agg,
            tol=cfg["tol"], seed=int(self.rng.integers(2**31)), fused=True,
            rounds="scan", summaries_backend=cfg["summaries"],
            pods=int(cfg["pods"]),
        )
        beta = coord.run(max_iter=cfg["max_rounds"])
        after = metrics.get(WIRE_COUNTER)
        return Answer(lam, np.asarray(beta, np.float64),
                      float(coord.trace[-1]), int(coord.iteration),
                      bool(coord.converged),
                      float(coord.reports[0].step_norm),
                      None if after is None else after - (before or 0.0))

    def release(self):
        """Each chip's peak memory to standard error, then free what the
        program holds."""
        pods = int(self.config["pods"])
        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in jax.devices()[:pods]]
        print(f"memory_peak_bytes per chip: {peaks}", file=sys.stderr)
        super().release()

    def kernel_work(self, answers) -> dict:
        """``single_fit``'s kernels over all sites, and ``pod_wire``: the
        bytes one chip's ring all-reduces move (``wire.ring_bytes``), one
        a round."""
        cfg, sh = self.config, self.config["shamir"]
        r = sum(a.rounds for a in answers)
        out = super().kernel_work(answers)
        out["pod_wire"] = r * wire.ring_bytes(
            cfg["features"], cfg["protect"], sh["residues"], sh["centers"],
            int(cfg["pods"]))
        return out
