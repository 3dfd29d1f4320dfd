"""Deterministic searches on a DeepSeek-sparse-attention MoE model,
against the sparse reference (``reference/sparse_latent.py``).

The ``search_latent`` check (every answer of the window must state the
design and point count of the reference's own search, and claim that
design's reference objective) on the chain whose attention is DeepSeek
sparse attention: the indexer whole on every head shard, a decode step
reading the index keys and the selected latent rows, and the split-KV
top-K merge.

A program whose plans do not state a design of every node of that chain
(one that maps the model under another mechanism, with ``mla`` where the
chain has ``dsa``) cannot run this configuration: the check stops the
run with a non-zero exit and no result line, before the reference
searches.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from checks.plan import rel_err
from checks.search_latent import covered
from reference.sparse_latent import SEARCHES, Problem


def problems(config: dict, traffic: dict, dtype) -> Dict[int, Problem]:
    return {i: Problem(config, v, v["objective"], dtype)
            for i, v in enumerate(traffic["variants"])}


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
    P64 = problems(config, traffic, np.float64)
    designs = {}
    for a in answers:
        if "plan" in a and claims is None:
            designs[a["index"]] = covered(a["plan"], P64[a["variant"]])
    used = {a["variant"] for a in answers if "plan" in a}
    ref = reference_answers(config, traffic, used)
    mismatch, worst = 0, 0.0
    for a in answers:
        if "plan" not in a:
            continue
        vi = a["variant"]
        P = P64[vi]
        if claims is None:
            d, points = designs[a["index"]], a["points"]
            claimed = a["plan"].objective_value
        else:
            d, points, claimed = claims[vi]
        r_design, r_points, _ = ref[vi]
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
