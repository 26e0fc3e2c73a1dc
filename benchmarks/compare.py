"""The comparisons that decide `correct`: readings of the timed path
against the plain reference's, each a plain number with a limit of its
own (benchmarks/limits/<cell>.json; PERF.md gives the readings each was
set from)."""

from __future__ import annotations

from statistics import median
from typing import Any, Dict, List

#: a leaf whose first gradient in the reference is under this share of
#: the median leaf's is nought to rounding (a key's bias under softmax):
#: under Adam it moves by round-off alone, so its change is not compared
TINY_GRADIENT = 1e-3


def _worst_gap(got: Dict[str, float], want: Dict[str, float],
               leaves) -> Dict[str, Any]:
    """Worst leaf by |norm - reference norm| over the larger of that
    leaf's reference norm and the median leaf's."""
    floor = median(want.values())
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in leaves}
    nan = [k for k, g in gaps.items() if g != g]
    where = nan[0] if nan else max(gaps, key=gaps.get)
    ranked = sorted(gaps.values())
    return {"value": float("inf") if nan else gaps[where], "leaf": where,
            "median_leaf": ranked[len(ranked) // 2]}


def _sketch_gap(got, want, norms, leaves) -> Dict[str, Any]:
    """Median leaf by the norm of the difference (estimated from the two
    sides' sketches: root mean square of the projections' differences)
    over the larger of the leaf's reference norm and the median leaf's.
    Norms are blind to rounding, which moves a tensor sideways and not
    in length; this is what the lower-precision control has to fail."""
    floor = median(norms.values())
    gaps = sorted(
        (sum((a - b) ** 2 for a, b in zip(got[k], want[k]))
         / len(want[k])) ** 0.5 / max(norms[k], floor) for k in leaves)
    mid = gaps[len(gaps) // 2]
    return {"value": float("inf") if mid != mid else mid,
            "worst_leaf": gaps[-1]}


def training(prog: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, Any]:
    """Each step's loss; the first gradient's norm and the parameters'
    change after three steps by the worst leaf's gap of norms, and by the
    median leaf's; and both again by the median leaf's norm of the
    difference."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], want["losses"]), 1):
        out[f"loss{i}_gap"] = {"value": abs(a - b) / abs(b)}
    out["grad_norm_gap"] = _worst_gap(prog["gnorm"], want["gnorm"],
                                      want["gnorm"])
    floor = TINY_GRADIENT * median(want["gnorm"].values())
    moved = [k for k, g in want["gnorm"].items() if g >= floor]
    out["change_norm_gap"] = _worst_gap(prog["dnorm"], want["dnorm"], moved)
    out["change_norm_gap"]["left_out"] = len(want["gnorm"]) - len(moved)
    # the same gaps by the median leaf: steady from seed to seed where
    # the worst leaf is the noise of one small leaf
    out["grad_norm_median"] = {"value": out["grad_norm_gap"]["median_leaf"]}
    out["change_norm_median"] = {
        "value": out["change_norm_gap"]["median_leaf"]}
    out["grad_diff_median"] = _sketch_gap(prog["gsketch"], want["gsketch"],
                                          want["gnorm"], want["gnorm"])
    out["change_diff_median"] = _sketch_gap(prog["dsketch"], want["dsketch"],
                                            want["dnorm"], moved)
    return out


def judge(numbers: Dict[str, Dict[str, Any]],
          limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """[{name, value, limit, ok}] for every number compared. A number
    with no limit in the cell's file is an error, not a pass."""
    rows = []
    for name, n in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits file")
        value, limit = n["value"], limits[name]
        if limit is None:  # named in PERF.md as not compared, with why
            continue
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": bool(value <= limit),
                     **{k: v for k, v in n.items() if k != "value"}})
    return rows
