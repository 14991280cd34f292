"""The comparisons that decide ``correct``: each number beside its limit."""
from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np


def check(value: float, limit: float, *, at_most: bool = True
          ) -> Dict[str, Any]:
    """One compared number: ``value <= limit`` (or ``>=``)."""
    ok = value <= limit if at_most else value >= limit
    return {"value": value, "limit": limit, "ok": bool(ok)}


def max_rel_gap(got: Sequence[float], want: Sequence[float]) -> float:
    """Largest |got - want| / |want| over paired readings."""
    if len(got) != len(want) or not want:
        return float("inf")
    return max(abs(a - b) / abs(b) for a, b in zip(got, want))


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float]) -> float:
    """Worst leaf's |norm(prog) - norm(ref)|, over the larger of that leaf's
    reference norm and the median leaf's; a leaf missing counts as 1."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog.get(k, 0.0) - ref[k]) / max(ref[k], med) for k in ref)
