"""The numbers that decide ``correct``, each against its limit.

Five numbers compare the program's first rounds with the reference's:

* ``loss_gap``: the largest relative gap between the program's and the
  reference's round loss over the rounds compared;
* ``grad_gap``: the worst leaf's gap between the norms of the first update
  the server optimizer received (the program's read from its AMSGrad state
  after round 1: ``m / (1 - beta1)``);
* ``change_gap``: the worst leaf's gap between the norms of the parameters'
  change over the rounds compared.  Leaves whose reference update is under
  a thousandth of the median leaf's move by round-off alone and are left
  out;
* ``grad_dir_gap`` and ``change_dir_gap``: the same two, with each leaf's
  dot product with a probe of N(0, 1) entries (``weights.probe_leaf``, the
  same on both sides) in place of its norm.  A norm barely sees which
  slots and signs a count-sketch used; the dot product sees the direction:
  ``dot_program - dot_reference`` is ``|program - reference|`` times an
  N(0, 1) draw.

A leaf's gap is ``|reading_program - reading_reference|`` over the larger
of the reference's norm of that leaf and of the median leaf.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "grad_dir_gap",
           "change_dir_gap")
MOVING = 1e-3       # a leaf moves if its reference update exceeds this x median


def leaf_gap(prog, ref, ref_norms, keep=None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    ref_norms = np.asarray(ref_norms, np.float64)
    den = np.maximum(ref_norms, np.median(ref_norms))
    gap = np.abs(prog - ref) / den
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap))


def numbers(prog: dict, ref: dict) -> dict:
    lp, lr = (np.asarray(prog["loss"], np.float64),
              np.asarray(ref["loss"], np.float64))
    g_ref, c_ref = ref["grad_norms"], ref["change_norms"]
    keep = np.asarray(g_ref) >= MOVING * np.median(g_ref)
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
           "grad_gap": leaf_gap(prog["grad_norms"], g_ref, g_ref),
           "change_gap": leaf_gap(prog["change_norms"], c_ref, c_ref, keep),
           "grad_dir_gap": leaf_gap(prog["grad_dots"], ref["grad_dots"],
                                    g_ref),
           "change_dir_gap": leaf_gap(prog["change_dots"],
                                      ref["change_dots"], c_ref, keep)}
    # a reading that is not a number fails its limit
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in NUMBERS}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
