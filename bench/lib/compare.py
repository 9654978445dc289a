"""The comparisons that decide ``correct``, and the verdict against limits."""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np


def rel_gap(got: float, want: float) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-30)


def leaf_norms(leaves: Iterable) -> List[float]:
    return [float(np.linalg.norm(np.asarray(x, np.float64).ravel())) for x in leaves]


def norm_gap(got: Iterable, want: Iterable, eligible: Optional[List[bool]] = None) -> float:
    """The worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's."""
    g, w = leaf_norms(got), leaf_norms(want)
    med = float(np.median(w)) if w else 0.0
    worst = 0.0
    for i, (a, b) in enumerate(zip(g, w)):
        if eligible is not None and not eligible[i]:
            continue
        gap = abs(a - b) / max(b, med, 1e-30)
        worst = gap if math.isnan(gap) else max(worst, gap)
        if math.isnan(worst):
            return worst
    return worst


def diff_gap(got: Iterable, want: Iterable) -> float:
    """The worst leaf's norm of the difference, over the larger of that
    leaf's reference norm and the median leaf's."""
    w = leaf_norms(want)
    med = float(np.median(w)) if w else 0.0
    d = leaf_norms(np.asarray(a, np.float64) - np.asarray(b, np.float64) for a, b in zip(got, want))
    return max((x / max(b, med, 1e-30) for x, b in zip(d, w)), default=0.0)


def moving_leaves(ref_grad_leaves: Iterable, share: float = 1e-3) -> List[bool]:
    """Leaves whose reference gradient is not nought to rounding: a norm of
    at least ``share`` of the median leaf's."""
    n = leaf_norms(ref_grad_leaves)
    med = float(np.median(n)) if n else 0.0
    return [x >= share * med for x in n]


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every number at or under its limit (NaN fails); a number without a
    limit, or a limit without a number, fails too."""
    checks, ok = {}, True
    for name in sorted(set(numbers) | set(limits)):
        v, lim = numbers.get(name, float("nan")), limits.get(name, float("nan"))
        ok = ok and bool(v <= lim)
        checks[name] = {"value": v, "limit": lim}
    return ok, checks
