"""The comparison that decides ``correct`` for a training cell.

The program's first steps and the plain reference's, from the same
weights and batches, are read alike:

* ``loss_gap``: the largest gap, in nats, between the program's loss
  and the reference's over the compared steps;
* ``grad_norm_gap``: of the first gradient as the optimizer got it, the
  worst leaf's gap between the two norms, over the larger of the
  reference's norm of that leaf and of the median leaf;
* ``update_norm_gap``: the same of each leaf's change over the compared
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).

A number that is not finite fails every limit.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Mapping, NamedTuple, Optional, Tuple

STILL = 1e-3        # a leaf under this share of the median gradient


class Readings(NamedTuple):
    losses: List[float]
    grad_norms: Dict[str, float]
    change_norms: Dict[str, float]


def _max(xs) -> float:
    xs = list(xs)
    return max(xs) if all(math.isfinite(x) for x in xs) else math.inf


def _worst_leaf(prog: Mapping[str, float], ref: Mapping[str, float],
                leaves) -> float:
    med = statistics.median(ref[k] for k in leaves)
    return _max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def numbers(prog: Readings, ref: Readings) -> Dict[str, float]:
    med = statistics.median(ref.grad_norms.values())
    moving = [k for k, g in ref.grad_norms.items() if g >= STILL * med]
    return {
        "loss_gap": _max(abs(p - r) for p, r in zip(prog.losses, ref.losses)),
        "grad_norm_gap": _worst_leaf(prog.grad_norms, ref.grad_norms,
                                     list(ref.grad_norms)),
        "update_norm_gap": _worst_leaf(prog.change_norms, ref.change_norms,
                                       moving),
    }


def judge(nums: Mapping[str, float], limits: Mapping[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """(correct, {name: {"value", "limit"}}) — every number under its
    limit; a non-finite number is reported as null."""
    checks = {k: {"value": (nums[k] if math.isfinite(nums[k]) else None),
                  "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(nums[k]) and nums[k] <= limits[k] for k in limits)
    return ok, checks


def finite_readings(r: Readings) -> Optional[str]:
    bad = [k for k, v in {**r.grad_norms, **r.change_norms}.items()
           if not math.isfinite(v)]
    if any(not math.isfinite(x) for x in r.losses):
        bad.append("loss")
    return ", ".join(sorted(set(bad))) or None
