"""The comparison that decides ``correct`` for training cells.

Numbers compared, each against the reference's reading of the same seed:

* ``loss{k}_gap``: |loss - reference loss| of step k, in nats;
* ``grad_gap``: by the worst leaf, the gap between the norms of the first
  gradient as each optimizer's state holds it after one step,
  |‖g‖ - ‖g_ref‖| over the larger of ‖g_ref‖ and the median leaf's;
* ``change_gap``: the same for each weight's change over the steps taken.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's move by round-off alone and are left out.

A number is compared where the cell's limits file gives it a limit; each is
printed beside its limit either way.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import numpy as np

QUIET_GRAD = 1e-3  # of the median leaf's reference gradient


def _norm_gap(ref: Dict[str, float], cand: Dict[str, float],
              keep=None) -> Tuple[float, str]:
    ids = [k for k in ref if keep is None or keep(k)]
    if set(ref) != set(cand):
        return math.inf, "leaves differ: " + ",".join(sorted(set(ref) ^ set(cand)))[:200]
    floor = float(np.median([ref[k] for k in ref]))
    worst, at = 0.0, ""
    for k in ids:
        gap = abs(cand[k] - ref[k]) / max(ref[k], floor)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, at = gap, k
    return worst, at


def compare(ref, cand) -> Dict[str, object]:
    """Readings (``bench.reference.train.Readings``) -> {number: value}, plus
    the worst leaves under ``*_leaf``."""
    out: Dict[str, object] = {}
    for k, (a, b) in enumerate(zip(cand.loss, ref.loss), start=1):
        gap = abs(a - b)
        out[f"loss{k}_gap"] = gap if math.isfinite(gap) else math.inf
    out["grad_gap"], out["grad_leaf"] = _norm_gap(ref.grad, cand.grad)
    med = float(np.median(list(ref.grad.values())))
    out["change_gap"], out["change_leaf"] = _norm_gap(
        ref.change, cand.change,
        keep=lambda k: ref.grad.get(k, 0.0) >= QUIET_GRAD * med,
    )
    return out


def judge(values: Dict[str, object], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, Optional[float]]]]:
    """(correct, {number: {"value", "limit"}}) over the numbers in ``values``."""
    shown, ok = {}, True
    for name, v in values.items():
        if name.endswith("_leaf"):
            continue
        lim = limits.get(name)
        shown[name] = {"value": v, "limit": lim}
        if lim is not None and not (v <= lim):
            ok = False
    missing = set(limits) - set(shown)
    if missing:
        raise ValueError(f"limits name numbers that were not read: {sorted(missing)}")
    return ok, shown
