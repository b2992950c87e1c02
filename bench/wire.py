"""The cross-chip wire of the pod fit: its least time and its device time.

A pod fit (``StudyCoordinator(pods=P)``) sums the pods' aggregated share
buffers once a round with one all-reduce over the pod mesh, under the
program's ``jax.named_scope("pod_wire")`` inside ``aggregate`` (the limb
split, the collective and the recombine).

* ``ICI_BYTES_PER_S``: each chip's inter-chip interconnect bandwidth,
  by ``device_kind``.  Google Cloud documentation, "TPU v5e": 1,600
  Gbit/s of chip-to-chip interconnect per chip.
* ``ring_bytes``: the bytes one chip moves in a ring all-reduce of the
  round's share buffer, ``2 (P - 1) / P`` times the buffer, counted as
  4-byte field words of the unpadded protected tree
  (``work.protected_elements``) whatever the wire's limb format, so a
  share of the wire's roofline can only read low.
* ``wire_seconds``: leaf-op device seconds of a reduced trace whose HLO
  instruction carries ``pod_wire`` on its ``op_name`` path, averaged
  over the devices; ``None`` where no op ran under it (a program without
  the pod wire).
"""
from __future__ import annotations

import re

from .scopes import hlo_index, live_hlo_texts
from .work import U32, protected_elements

__all__ = ["ICI_BYTES_PER_S", "SCOPE", "ring_bytes", "wire_seconds"]

ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}
SCOPE = "pod_wire"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r" ([\w\-]+)\(")


def ring_bytes(features: int, protect: str, residues: int, centers: int,
               pods: int) -> float:
    """Bytes one chip moves in one round's ring all-reduce."""
    words = centers * residues * protected_elements(features, protect)
    return 2.0 * (pods - 1) / pods * U32 * words


def _on_wire(text: str) -> bool:
    m = _OP_NAME.search(text)
    return bool(m) and SCOPE in m.group(1).split("/")


def _head(text: str) -> str:
    """An instruction's text up to its opcode, as the trace prints it."""
    m = _OPCODE.search(text)
    return text[:m.end()] if m else text


def wire_seconds(summary, texts=None) -> float | None:
    """Device seconds under ``pod_wire``, averaged over devices."""
    index = hlo_index(live_hlo_texts() if texts is None else texts)
    total, found = 0.0, False
    for name, secs in summary.op_s.items():
        head, sep, rest = name.partition(" = ")
        entries = index.get(head.strip().lstrip("%"), []) if sep else []
        flags = {_on_wire(text) for text, _ in entries}
        if len(flags) > 1:  # the same name in several programs
            flags = {_on_wire(text) for text, _ in entries
                     if _head(text) == _head(rest) or text.startswith(rest)}
        if flags == {True}:
            total += secs
            found = True
    return total / summary.devices if found else None
