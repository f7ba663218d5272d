"""The comparison that decides ``correct``.

Both sides give the same readings of the first rounds of a run: each
round's mean local and distill loss, each client's accuracy and ID
fraction, the per-leaf norm of the optimizer's momentum after round 0
(the first gradient as the optimizer holds it), and the per-leaf norm of
the parameters' change over the followed rounds. The numbers:

* ``loss_gap``: the largest relative gap of a round's loss;
* ``grad_gap`` / ``update_gap``: the worst leaf's gap between the two
  norms, against the reference's norm of that leaf or of the median leaf,
  whichever is larger. ``update_gap`` leaves out the leaves whose
  reference gradient is under a thousandth of the median leaf's: they
  move by rounding alone (a conv bias before BN, BN's stored statistics);
* ``acc_gap``: the largest gap of a client's accuracy;
* ``id_gap``: the largest gap of a client's ID fraction.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "update_gap", "acc_gap", "id_gap")
TINY_GRAD = 1e-3


def _leaf_gap(prog, ref, keep=None) -> float:
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    if keep is not None:
        p, r = p[keep], r[keep]
    if not len(r):
        return 0.0
    den = np.maximum(r, np.median(r))
    den = np.where(den > 0, den, 1.0)
    return float(np.max(np.abs(p - r) / den))


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Every compared number; NaN where the program's readings are not
    finite or its leaves do not line up with the reference's."""
    if prog.get("leaf_shapes") != ref["leaf_shapes"]:
        return {k: math.nan for k in NUMBERS}
    losses = []
    for key in ("local_loss", "distill_loss"):
        for p, r in zip(prog[key], ref[key]):
            losses.append(abs(p - r) / max(abs(r), 1e-12))
    keep = np.asarray(ref["grad_norms"]) >= TINY_GRAD * np.median(
        ref["grad_norms"])
    out = {
        "loss_gap": max(losses),
        "grad_gap": _leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "update_gap": _leaf_gap(prog["change_norms"], ref["change_norms"],
                                keep),
        "acc_gap": float(np.max(np.abs(np.asarray(prog["accs"])
                                       - np.asarray(ref["accs"])))),
        "id_gap": float(np.max(np.abs(np.asarray(prog["id_fracs"])
                                      - np.asarray(ref["id_fracs"])))),
    }
    return {k: (v if math.isfinite(v) else math.nan) for k, v in out.items()}


def verdict(nums: Dict[str, float], limits: Dict
            ) -> Tuple[bool, Dict[str, Dict[str, float]], List[str]]:
    """``limits["limits"]`` holds one limit per compared number; numbers
    listed under ``limits["not_compared"]`` are printed but decide
    nothing. A NaN fails."""
    checks, lines, ok = {}, [], True
    for name in NUMBERS:
        v = nums[name]
        if name in limits["limits"]:
            lim = float(limits["limits"][name])
            good = math.isfinite(v) and v <= lim
            ok &= good
            checks[name] = {"value": v, "limit": lim}
            lines.append(f"check {name} {v!r} limit {lim!r} "
                         f"{'ok' if good else 'FAIL'}")
        else:
            checks[name] = {"value": v, "limit": None}
            lines.append(f"check {name} {v!r} not compared")
    return ok, checks, lines
