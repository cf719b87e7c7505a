"""The comparison that decides `correct`.

The program's outputs over the compared steps, held against the plain
reference (`reference.py`, float64) run from the same seed over the same
steps:

  raster_mismatch  (neuron, step) pairs whose spike differs, all steps
  v_gap_mv         largest |v - v_ref| over neurons after the last one
  u_gap            largest |u - u_ref| over neurons after the last one
  w_gap            largest |w - w_ref| over synapses after the last one

Each number has its limit in `checks/<cell>.json`; a run is correct when
no number is above its limit.  `PERF.md` gives the readings each
limit was set from.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict

import numpy as np

NUMBERS = ("raster_mismatch", "v_gap_mv", "u_gap", "w_gap")


@dataclasses.dataclass
class Outputs:
    """What a run produced: raster [T, N] bool; v, u [N]; w [E] in the
    program's (target, source, slot) order."""

    raster: np.ndarray
    v: np.ndarray
    u: np.ndarray
    w: np.ndarray

    def digest(self) -> str:
        """sha256 over the four arrays' bytes, in field order."""
        h = hashlib.sha256()
        for a in (self.raster, self.v, self.u, self.w):
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()


def readings(got: Outputs, want: Outputs) -> Dict[str, float]:
    if got.raster.shape != want.raster.shape or got.w.shape != want.w.shape:
        raise ValueError(f"shapes differ: raster {got.raster.shape} vs "
                         f"{want.raster.shape}, w {got.w.shape} vs "
                         f"{want.w.shape}")

    def gap(a, b):
        return float(np.max(np.abs(np.asarray(a, np.float64)
                                   - np.asarray(b, np.float64))))

    return dict(raster_mismatch=int(np.count_nonzero(got.raster
                                                     != want.raster)),
                v_gap_mv=gap(got.v, want.v), u_gap=gap(got.u, want.u),
                w_gap=gap(got.w, want.w))


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value": x, "limit": l}}); a missing or
    non-finite number fails."""
    checks = {k: {"value": numbers.get(k), "limit": limits[k]}
              for k in NUMBERS}
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
