"""The comparison that decides ``correct``.

Three numbers, each against its own limit from ``bench/limits/<cell>.json``:

- ``loss_gap``: the widest relative gap between the program's loss and the
  reference's over every step the program took;
- ``grad_gap``: over the leaves, the widest gap between the norm of the
  program's first gradient (as the optimizer took it) and the reference's,
  against the reference's norm of that leaf or of the median leaf, whichever
  is larger;
- ``update_gap``: the same for the parameters' change after the last step,
  over the leaves whose reference gradient is at least a thousandth of the
  median leaf's (a leaf with no gradient moves by round-off alone).
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

#: a leaf whose first reference gradient is under this share of the median
#: leaf's has no gradient to rounding; its change is left out
MOVED = 1e-3


def _norm_gap(prog, ref, keep=None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    floor = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / floor))


def numbers(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    raw = np.asarray(ref["grad_raw"], np.float64)
    moved = raw >= MOVED * np.median(raw)
    gaps = np.abs(lp - lr) / np.abs(lr)
    return {
        "loss_gap": float(np.max(gaps)) if np.all(np.isfinite(gaps)) else float("inf"),
        "grad_gap": _norm_gap(prog["grad"], ref["grad"]),
        "update_gap": _norm_gap(prog["delta"], ref["delta"], moved),
    }


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Any]:
    """``{name: {"value", "limit"}}`` and whether every value is within."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in nums}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return {"checks": checks, "correct": bool(ok)}
