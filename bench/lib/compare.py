"""The numbers that decide ``correct``, and the check against limits.

Training: each step's loss (relative gap), the per-leaf norm of the first
gradient and the per-leaf norm of the weights' change after the checked
steps.  A leaf's number is the gap between the program's norm and the
reference's, over the larger of the reference's norm of that leaf and the
median leaf's; the worst leaf counts.  Leaves whose reference gradient is
under a thousandth of the median leaf's (nought to rounding, such as a
key bias under softmax) are left out of the change.
"""
from __future__ import annotations

import math
import statistics

STILL_FRACTION = 1e-3


def loss_gap(prog: list, ref: list) -> float:
    if len(prog) != len(ref):
        raise ValueError(f"{len(prog)} program losses vs {len(ref)}")
    return max(abs(p - r) / abs(r) if math.isfinite(p) else math.inf
               for p, r in zip(prog, ref))


def leaf_gaps(prog: dict, ref: dict, names=None) -> dict:
    """{leaf: gap} over ``names`` (default: every reference leaf).  A leaf
    the program lacks, or a non-finite norm, reads inf."""
    med = statistics.median(ref.values())
    out = {}
    for n in (names if names is not None else ref):
        p = prog.get(n, math.nan)
        out[n] = (abs(p - ref[n]) / max(ref[n], med)
                  if math.isfinite(p) else math.inf)
    return out


def moving_leaves(ref_grad: dict) -> list:
    med = statistics.median(ref_grad.values())
    return [n for n, v in ref_grad.items() if v >= STILL_FRACTION * med]


def train_numbers(prog: dict, ref: dict) -> dict:
    """Each number a limit may hold: the loss gap, and of the gradient and
    the change the worst leaf's gap (with its leaf) and the median leaf
    gap."""
    g = leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    c = leaf_gaps(prog["change_norms"], ref["change_norms"],
                  moving_leaves(ref["grad_norms"]))
    g_at, c_at = max(g, key=g.get), max(c, key=c.get)
    return {"loss_gap": {"value": loss_gap(prog["losses"], ref["losses"])},
            "grad_gap": {"value": g[g_at], "leaf": g_at},
            "change_gap": {"value": c[c_at], "leaf": c_at},
            "grad_gap_median": {"value": statistics.median(g.values())},
            "change_gap_median": {"value": statistics.median(c.values())}}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every limited number at or under its limit; a
    limit whose number is missing or not finite is not met."""
    checks, ok = {}, True
    for name, lim in limits["limits"].items():
        v = numbers.get(name, {}).get("value", math.nan)
        passed = math.isfinite(v) and v <= lim
        ok &= passed
        checks[name] = {"value": v, "limit": lim}
    return bool(ok), checks
