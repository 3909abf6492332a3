"""The comparison that decides ``correct``: every answer of the window against
the plain reference (`bench.reference`).

Numbers, each held to a limit of the deployment's file (``correct``):

* ``unanswered``: requests of the window that raised or got no answer within
  the drain limit. Limit 0.
* ``objective_gap``: the widest gap between the objective the service reported
  for an answer (`Completion.objective`) and the reference's eq. 13 of that
  answer, as a share of the sum of the sizes of eq. 13's terms. It sees the
  scoring kernel, padding into the bucket and `unpad_alloc`, and which batch
  slot an answer came from.
* ``violation``: the largest relative violation of P1's constraints by any
  answer. It sees the solve and refine programs' hardening and repair steps.
* ``gain``: the median over the window's answers of the share of the
  equal-share allocation's distance to the least possible objective L that
  the answer closed, (obj_equal - obj) / (obj_equal - L). It sees whether
  the solve optimised, and how far: an under-converged solve closes less.
  The median, not the sum, because a few requests with devices at the
  cell's edge dominate a sum and make it swing from seed to seed. Higher is
  sound, so its limit is a floor.

``control=True`` gives the control's readings: the same answers as a
bfloat16 path would return them, scored by the reference in bfloat16.
"""
from __future__ import annotations

import numpy as np

from . import reference as ref

NAMES = ("unanswered", "objective_gap", "violation", "gain")
#: numbers whose limit is a floor: the check fails below it
FLOORS = ("gain",)


def readings(dep: dict, req: ref.Requests, ans: ref.Answers, reported,
             unanswered: int, control: bool = False) -> dict:
    """The compared numbers for answered requests ``req``/``ans``."""
    if control:
        ans = ref.in_bf16(ans)
        reported, _ = ref.objective(dep, req, ans, dtype=ref.BF16)
    obj, size = ref.objective(dep, req, ans)
    gap = np.abs(np.asarray(reported, np.float64) - obj) / np.maximum(size, 1e-30)
    least = ref.least_objective(dep)
    base, _ = ref.objective(dep, req, ref.equal_share(dep, req))
    return {
        "unanswered": float(unanswered),
        "objective_gap": float(np.max(gap)) if len(gap) else float("nan"),
        "violation": float(np.max(ref.violation(dep, req, ans))) if len(gap) else float("nan"),
        "gain": float(np.median((base - obj) / (base - least))) if len(gap) else float("nan"),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit. A number that is not
    finite, or on the wrong side of its limit, fails."""
    out = {}
    ok = True
    for name in NAMES:
        v, lim = values[name], limits[name]
        floor = name in FLOORS
        out[name] = {"value": v, ("floor" if floor else "limit"): lim}
        ok = ok and bool(np.isfinite(v)) and (v >= lim if floor else v <= lim)
    return ok, out
