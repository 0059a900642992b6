"""The numbers that decide ``correct``, each against its limit."""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np


class Check:
    """Named readings, each held to the limit the cell file states."""

    def __init__(self, limits: Dict[str, float]):
        self.limits = dict(limits)
        self.readings: Dict[str, float] = {}

    def add(self, name: str, value: float) -> None:
        if name not in self.limits:
            raise KeyError(f"no limit for check {name!r}; the cell file "
                           f"states {sorted(self.limits)}")
        self.readings[name] = float(value)

    @property
    def correct(self) -> bool:
        return bool(self.readings) and all(
            math.isfinite(v) and v <= self.limits[k]
            for k, v in self.readings.items())

    def lines(self) -> List[str]:
        return [f"check {k}={v!r} limit={self.limits[k]!r}"
                for k, v in self.readings.items()]

    def as_json(self) -> Dict[str, Dict[str, float]]:
        return {k: {"value": v, "limit": self.limits[k]}
                for k, v in self.readings.items()}


def rel_gap(a: float, b: float) -> float:
    """|a - b| / |b|."""
    return abs(a - b) / max(abs(b), 1e-30)


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   ref_grad: Dict[str, float]) -> Tuple[float, str, int]:
    """Worst gap between the program's and the reference's norm of a
    leaf, over the leaf's reference norm or the median leaf's, whichever
    is larger. Leaves whose reference gradient is under a thousandth of
    the median leaf's are left out. Returns (gap, worst leaf, leaves
    left out)."""
    med_grad = float(np.median(list(ref_grad.values())))
    kept = [k for k in ref if ref_grad[k] >= 1e-3 * med_grad]
    med = float(np.median([ref[k] for k in kept]))
    worst, where = 0.0, ""
    for k in kept:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not math.isfinite(g):
            return math.inf, k, len(ref) - len(kept)
        if g >= worst:
            worst, where = g, k
    return worst, where, len(ref) - len(kept)
