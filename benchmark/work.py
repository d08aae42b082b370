"""The work a call needs, computed from shapes, and the chip's peaks.

The join's bytes are those of the logical work, not of an
implementation: every input replica read once and the fixpoint written
once.  A pairwise tree that writes its intermediates, and a fused fold
that never does, are held to the same count, so a fused fold reads
under 100% of the roofline and not over it.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def join_bytes(replicas_in: int, objects: int, bytes_per_object: int) -> int:
    """HBM bytes a join of ``replicas_in`` replicas of ``objects``
    objects needs at least: each input read once, the result written
    once."""
    return (int(replicas_in) + 1) * int(objects) * int(bytes_per_object)


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_pct(nbytes: float, seconds: float, device_kind: str,
                 chips: int = 1) -> float | None:
    """The share of ``chips`` chips' HBM bandwidth that moving
    ``nbytes`` in ``seconds`` of device time reaches, in percent.
    None where no device time was read."""
    if seconds <= 0:
        return None
    least_s = nbytes / (chips * peaks(device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
