"""Deterministic searches: every answer against the reference's search.

The reference runs each variant's search once (float64); every answer of
the window for that variant must state the reference's design and point
count (``design_mismatch``), and the objective its plan claims must be
the reference's objective of the design it states (``objective_rel_err``,
infinite when the reference finds that design infeasible).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from checks.plan import design_of, problems, rel_err
from reference.search import SEARCHES


def reference_answers(config: dict, traffic: dict, used, dtype=np.float64):
    """The reference's (design, points, objective) for each variant used."""
    search = SEARCHES[traffic["optimiser"]]
    out = {}
    for vi, P in problems(config, traffic, dtype).items():
        if vi in used:
            d, points = search(P, **traffic.get("kwargs", {}))
            out[vi] = (d, points, P.objective_of(d)[0])
    return out


def check(answers: List[dict], config: dict, traffic: dict,
          claims=None) -> Dict[str, float]:
    """``claims`` (the control) replaces the program's answers by
    ``{variant: (design, points, objective)}``."""
    used = {a["variant"] for a in answers if "plan" in a}
    ref = reference_answers(config, traffic, used)
    P64 = problems(config, traffic, np.float64)
    mismatch, worst = 0, 0.0
    for a in answers:
        if "plan" not in a:
            continue
        vi = a["variant"]
        P = P64[vi]
        if claims is None:
            d, points = design_of(a["plan"], P), a["points"]
            claimed = a["plan"].objective_value
        else:
            d, points, claimed = claims[vi]
        r_design, r_points, _ = ref[vi]
        if d is None:
            mismatch += 1
            worst = float("inf")
            continue
        mismatch += int(d != r_design or points != r_points)
        obj, feasible = P.objective_of(d)
        worst = max(worst, rel_err(claimed, obj, feasible))
    return {"design_mismatch": mismatch, "objective_rel_err": worst}


def control_claims(answers: List[dict], config: dict, traffic: dict,
                   cache: dict):
    """The reference at bfloat16 in the program's place: its own search
    answers each variant (run once per variant into ``cache``)."""
    import ml_dtypes
    used = {a["variant"] for a in answers if "plan" in a} - set(cache)
    if used:
        cache.update(reference_answers(config, traffic, used,
                                       ml_dtypes.bfloat16))
    return cache
