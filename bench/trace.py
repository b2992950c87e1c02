"""Reduce a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes with
nothing but JAX: from each TPU plane the events of its ``XLA Ops`` line
(one event per operation that ran on the device, named after the HLO
instruction or the Pallas kernel), and from the host's ``python`` thread
the harness's own ``jax.profiler.TraceAnnotation`` spans and those of the
program's span tracer (``repro.obs.trace``, on in a traced run).  The
profiler puts all of them on one clock.

``reduce`` then gives, inside the harness's window span:

* the union of device-op intervals (busy time), per device and averaged;
* each operation name's summed device time, over the events that hold
  no other event (a loop's event spans its body's);
* every idle gap of the device, attributed to the innermost span that
  holds it (a program span such as ``StudyCoordinator.step_block``, else
  ``bench.job`` while a job runs, ``bench.window`` between jobs);
* per job, the part of its span in which no device op ran.

A ``Trace`` also loads from JSON, which is how a small recorded trace is
kept for the tests.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os

__all__ = ["Trace", "Summary", "load", "find_xplane", "reduce",
           "WINDOW_SPAN", "JOB_SPAN"]

WINDOW_SPAN = "bench.window"
JOB_SPAN = "bench.job"
OPS_LINE = "XLA Ops"
NAME_CHARS = 160  # an op's name is its HLO text: keep its head


@dataclasses.dataclass
class Trace:
    # per device: [(name, start_ns, end_ns)] of every device operation
    ops: list
    # [(name, start_ns, end_ns)] of the harness's spans on the host
    spans: list

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        raw = json.loads(text)
        return cls([[tuple(e) for e in dev] for dev in raw["ops"]],
                   [tuple(s) for s in raw["spans"]])


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, program_spans=()) -> Trace:
    """The device ops and the spans named ``bench.*`` or in
    ``program_spans``."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.extend((e.name[:NAME_CHARS], int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events)
            ops.append(sorted(dev, key=lambda e: e[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                             for e in line.events
                             if e.name.startswith("bench.")
                             or e.name in program_spans)
    return Trace(ops, sorted(spans, key=lambda s: s[1]))


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(merged, lo, hi) -> int:
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def _leaves(ops):
    """The ops that hold no other op: a ``while`` or ``conditional`` event
    spans the events of its body, which are counted instead."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt[1] >= o[2]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over devices
    op_s: dict  # op name -> summed device seconds of its leaf events
    gaps: list  # [(span name, seconds)] every idle gap, longest first
    job_host_s: list  # per job span: seconds with no device op running
    devices: int


def reduce(trace: Trace) -> Summary:
    windows = [s for s in trace.spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, got "
                         f"{len(windows)}")
    _, lo, hi = windows[0]
    devices = [dev for dev in trace.ops if dev]
    if not devices:
        raise ValueError("the trace holds no device operation")
    jobs = [s for s in trace.spans if s[0] == JOB_SPAN and lo <= s[1] < hi]
    merged = [_union(dev, lo, hi) for dev in devices]
    busy = sum(_covered(m, lo, hi) for m in merged) / len(merged)
    op_s = {}
    for dev in devices:
        for name, s, e in _leaves(dev):
            if s >= lo and e <= hi:
                op_s[name] = op_s.get(name, 0.0) + (e - s) * 1e-9
    inner = sorted((s for s in trace.spans
                    if s[0] != WINDOW_SPAN and lo <= s[1] < hi),
                   key=lambda s: s[2] - s[1])
    gaps = []
    for m in merged:
        edges = [lo] + [x for iv in m for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            owner = next((s[0] for s in inner if s[1] <= mid < s[2]),
                         WINDOW_SPAN)
            gaps.append((owner, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    job_host = [((e - s) - sum(_covered(m, s, e) for m in merged)
                 / len(merged)) * 1e-9 for _, s, e in jobs]
    return Summary((hi - lo) * 1e-9, busy * 1e-9, op_s, gaps,
                   job_host, len(devices))
