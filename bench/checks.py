"""The comparison that decides `correct`: the program's first cycle
against the plain reference, by three numbers.

  loss1_gap relative gap of the first step's loss: the forward pass and
            loss at the seeded weights, before any update can amplify a
            difference
  loss_gap  largest relative gap of a step's loss (all steps of the cycle)
  mom_gap   momentum after the cycle, which holds every gradient the
            optimizer got in it, by the worst leaf and replica
  upd_gap   parameter change over the cycle, by the worst leaf and replica
  div_gap   distance of each replica's parameters from replica 0's after
            the cycle, which the exchange shrinks, by the worst leaf
  sent_gap  the exchange's result (the replica mean the send left in
            flight) less the initial weights, by the worst leaf and replica
  sent_spread  how far each replica's copy of that result lies from
            replica 0's, over the result's size as in sent_gap: an
            exchange gives every replica the same mean, so the reference
            reads 0 and so does a sound program, exactly

A leaf's gap is the gap between the program's norm and the reference's
(not the norm of their difference), over the reference's norm of that
leaf or of the median leaf, whichever is larger, since some leaves hold
almost nothing. Leaves whose reference momentum is under a thousandth of
the median leaf's are left out, by that rule and not by name: they move
by round-off alone."""
from __future__ import annotations

import math

import numpy as np

CHECKS = ("loss1_gap", "loss_gap", "mom_gap", "upd_gap", "div_gap",
          "sent_gap", "sent_spread")
NEGLIGIBLE = 1e-3


def _leaf_gap(prog: list, ref: list, keep) -> float:
    worst = 0.0
    for p_r, q_r in zip(prog, ref):
        med = float(np.median(list(q_r.values())))
        for leaf in keep:
            denom = max(q_r[leaf], med)
            gap = abs(p_r[leaf] - q_r[leaf]) / denom if denom > 0 else (
                0.0 if p_r[leaf] == q_r[leaf] else math.inf)
            worst = max(worst, gap if math.isfinite(p_r[leaf]) else math.inf)
    return worst


def _spread(prog: list, ref: list, scale: list, keep) -> float:
    worst = 0.0
    for p_r, q_r, s_r in zip(prog, ref, scale):
        med = float(np.median(list(s_r.values())))
        for leaf in keep:
            denom = max(s_r[leaf], med)
            gap = abs(p_r[leaf] - q_r[leaf])
            worst = max(worst, gap / denom if denom > 0 else
                        (0.0 if gap == 0 else math.inf))
    return worst


def readings(prog: dict, ref: dict) -> dict:
    """{check: number} for a program record against a reference record
    (both as `bench.reference.run` returns them)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not np.all(np.isfinite(lp)):
        loss_gap = math.inf
    med = [float(np.median(list(m.values()))) for m in ref["mom"]]
    keep = [leaf for leaf in ref["mom"][0]
            if all(m[leaf] >= NEGLIGIBLE * md
                   for m, md in zip(ref["mom"], med))]
    return {"loss1_gap": float(abs(lp[0] - lr[0]) / abs(lr[0])),
            "loss_gap": loss_gap,
            "mom_gap": _leaf_gap(prog["mom"], ref["mom"], keep),
            "upd_gap": _leaf_gap(prog["upd"], ref["upd"], keep),
            "div_gap": _leaf_gap(prog["div"][1:], ref["div"][1:], keep),
            "sent_gap": _leaf_gap(prog["sent"], ref["sent"], keep),
            "sent_spread": _spread(prog["spread"], ref["spread"],
                                   ref["sent"], keep)}


def judge(values: dict, limits: dict) -> bool:
    """True when every number with a limit lies within it. A number whose
    limit is None has no upper reading in that cell (nothing it is meant
    to catch reads higher): it is printed, not compared."""
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in CHECKS if limits[k] is not None)
