"""Reading the design back out of an exported plan, and scoring it."""
from __future__ import annotations

import math
from typing import Dict, Optional

from reference.model import Design, Problem


def design_of(plan, P: Problem) -> Optional[Design]:
    """The design a plan states: its partitions give the cuts, and each
    partition's fold triple of a node kind gives every node of that kind
    in it (the spmd backend ties them). None when the plan does not cover
    the reference's node chain."""
    si, so, kk = [0] * P.n, [0] * P.n, [0] * P.n
    covered, cuts = [], []
    for part in plan.partitions:
        nodes = list(part.node_indices)
        covered += nodes
        for j in nodes:
            if j >= P.n:
                return None
            kp = part.kinds.get(P.nodes[j].kind)
            if kp is None:
                return None
            si[j], so[j], kk[j] = kp.s_in, kp.s_out, kp.kern
        if nodes:
            cuts.append(nodes[-1])
    if covered != list(range(P.n)):
        return None
    return Design(tuple(cuts[:-1]), tuple(si), tuple(so), tuple(kk))


def rel_err(claimed: Optional[float], exact: float, feasible: bool) -> float:
    """Relative gap of a claimed objective; infinite for a design the
    reference finds infeasible or a claim that is missing."""
    if claimed is None or not feasible or not math.isfinite(exact):
        return math.inf
    return abs(float(claimed) - exact) / max(abs(exact), 1e-300)


def problems(config: dict, traffic: dict, dtype) -> Dict[int, Problem]:
    return {i: Problem(config, v, v["objective"], dtype)
            for i, v in enumerate(traffic["variants"])}
