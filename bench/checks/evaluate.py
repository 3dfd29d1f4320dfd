"""Stochastic searches: every answer's design scored by the reference.

Simulated annealing follows the device's random stream, so no reference
search exists. Each answer's design is scored by the float64 reference:
the objective the plan claims (``objective_rel_err``) and the device's own
float32 objective of its best design (``device_rel_err``) must both be
that design's objective, and the design must be feasible (an infeasible
one reads infinite on both).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from checks.plan import design_of, problems, rel_err


def check(answers: List[dict], config: dict, traffic: dict,
          claims=None) -> Dict[str, float]:
    """``claims`` (the control) maps an answer's index to the objectives
    (plan, device) it claims in place of the program's."""
    P64 = problems(config, traffic, np.float64)
    plan_err, dev_err = 0.0, 0.0
    for a in answers:
        if "plan" not in a:
            continue
        P = P64[a["variant"]]
        d = design_of(a["plan"], P)
        if d is None:
            plan_err = dev_err = float("inf")
            continue
        obj, feasible = P.objective_of(d)
        claimed, device = (a["plan"].objective_value, a["device_objective"]) \
            if claims is None else claims[a["index"]]
        plan_err = max(plan_err, rel_err(claimed, obj, feasible))
        dev_err = max(dev_err, rel_err(device, obj, feasible))
    return {"objective_rel_err": plan_err, "device_rel_err": dev_err}


def control_claims(answers: List[dict], config: dict, traffic: dict,
                   cache: dict):
    """The reference at bfloat16 in the program's place: each answer's
    design scored in bfloat16 stands for both of its claims (``cache`` is
    not needed: every answer is scored anew)."""
    import ml_dtypes
    P16 = problems(config, traffic, ml_dtypes.bfloat16)
    out = {}
    for a in answers:
        if "plan" in a:
            P = P16[a["variant"]]
            d = design_of(a["plan"], P)
            obj = P.objective_of(d)[0] if d is not None else None
            out[a["index"]] = (obj, obj)
    return out
