"""Plain reference of the deterministic searches: rule-based and brute force.

Each function takes a reference ``Problem`` (``model.py``) and the
optimiser's arguments as the traffic file gives them, and returns the
design the search defines and the number of design points it evaluates.
They follow the searches as the mapping optimiser specifies them:

- rule-based (SAMO Algorithm 2, TPU edition): repair the fully split
  design into feasibility, descend each partition greedily (slowest node
  first; among its strictly improving fold triples, the one with the
  smallest collective-bytes then residency increase), refine six uniform
  seed designs the same way, keep the best, then merge neighbouring
  partitions while the objective improves, remove cuts, and descend once
  more;
- brute force: every combination of fold values over the tied decision
  slots, in product order (last slot fastest), up to ``max_points``; the
  first design with the lowest feasible objective wins.

Simulated annealing draws its moves from the device's random stream, so
it has no reference search: its designs are checked by evaluation alone.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .model import VARS, Design, Problem

#: improvement threshold of the greedy descent, in seconds
DESCENT_EPS = 1e-15
#: residency improvement a repair move must make, in bytes
REPAIR_EPS = 1e-9


# ----------------------------------------------------------------------
# feasibility repair
# ----------------------------------------------------------------------

def _residency(P: Problem, d: Design):
    s = P.one(d)
    parts = P.partitions(d.cuts)
    res = [float(s.part_resident[0, p]) for p in range(len(parts))]
    worst = max(range(len(parts)), key=lambda p: res[p])
    return parts, res, worst, s


def repair(P: Problem, d: Design, max_steps: int = 1024) -> Design:
    """Raise folds of the fattest nodes of the most resident partition
    while that lowers its residency; split it at its middle when no fold
    helps."""
    base = int(P.one(d).structural[0])
    for _ in range(max_steps):
        if P.objective_of(d)[1]:
            return d
        parts, res, wi, s = _residency(P, d)
        worst, worst_res = parts[wi], res[wi]
        order = sorted(worst, key=lambda i: -float(s.resident[0, i]))
        best = None
        for i in order:
            for var in ("s_out", "kern", "s_in"):
                higher = [v for v in P.menus[var][i] if v > d.fold(var)[i]]
                if not higher:
                    continue
                d2 = P.set_fold(d, i, var, higher[0])
                if int(P.one(d2).structural[0]) > base:
                    continue
                parts2, res2, _, _ = _residency(P, d2)
                p2 = next(p for p, nodes in enumerate(parts2)
                          if worst[0] in nodes)
                if res2[p2] < worst_res - REPAIR_EPS and (
                        best is None or res2[p2] < best[0]):
                    best = (res2[p2], d2)
            if best is not None:
                break
        if best is not None:
            d = best[1]
            continue
        inner = [e for e in P.cut_edges
                 if e not in d.cuts and worst[0] <= e < worst[-1]]
        if not inner:
            return d
        d = d.with_cuts(set(d.cuts) | {inner[len(inner) // 2]})
    return d


# ----------------------------------------------------------------------
# rule-based
# ----------------------------------------------------------------------

def _t_conf(P: Problem, part: Sequence[int], d: Design) -> float:
    w = 0.0
    for i in part:
        w += P.nodes[i].weight_bytes / d.s_out[i]
    return P.plat["reconf_fixed_s"] + w / P.plat["dma_bw"]


def descend(P: Problem, d: Design, part: Sequence[int]) -> Tuple[Design, int]:
    """Greedy descent of one partition; returns the design and the number
    of probes evaluated."""
    points = 0
    blocked = set()
    pidx = next(p for p, nodes in enumerate(P.partitions(d.cuts))
                if nodes[0] == part[0])
    amort = 1.0 if P.objective == "latency" \
        else 1.0 / max(P.amortisation, 1)
    for _ in range(max(512, 16 * len(part))):
        left = [i for i in part if i not in blocked]
        if not left:
            break
        times = P.one(d).node_time[0]
        j = max(left, key=lambda i: float(times[i]))
        cur = (d.s_in[j], d.s_out[j], d.kern[j])
        probes = []
        for trip in itertools.product(*(P.menus[v][j] for v in VARS)):
            if trip != cur and P.mesh.realizable(*trip):
                d2 = d
                for var, val in zip(VARS, trip):
                    d2 = P.set_fold(d2, j, var, val)
                probes.append(d2)
        best = None
        if probes:
            s = P.score([d] + probes)
            points += len(probes)

            def cost(r: int, dd: Design) -> float:
                t = float(s.part_time[r, pidx])
                if pidx > 0:
                    t += amort * _t_conf(P, part, dd)
                return t

            t_now = cost(0, d)
            coll = s.collective.sum(axis=1)
            resd = s.resident.sum(axis=1)
            for r, d2 in enumerate(probes, start=1):
                if not s.feasible[r] or cost(r, d2) >= t_now - DESCENT_EPS:
                    continue
                dr = (float(coll[r]) - float(coll[0]),
                      float(resd[r]) - float(resd[0]))
                if best is None or dr < best[0]:
                    best = (dr, d2)
        if best is None:
            blocked.add(j)
            continue
        d = best[1]
        for i in P.scope(j, d.cuts):
            blocked.discard(i)
    return d, points


def _seeds(P: Problem) -> List[Design]:
    """Uniform fold triples over the whole graph that use at least a
    quarter of the mesh, each clamped per node and repaired."""
    vals, chips = P.mesh.fold_values, P.mesh.chips
    out = []
    for trip in itertools.product(vals, vals, vals):
        prod = trip[0] * trip[1] * trip[2]
        if prod > chips or not P.mesh.realizable(*trip) or prod < chips // 4:
            continue
        ones = (1,) * P.n
        d = Design((), ones, ones, ones)
        for j in range(P.n):
            for var, val in zip(VARS, trip):
                d = P.set_fold(d, j, var, val)
        out.append(repair(P, d))
    return out


def rule_based(P: Problem, multi_start: bool = True) -> Tuple[Design, int]:
    points = 0
    d = repair(P, P.initial())
    for part in P.partitions(d.cuts):
        d, p = descend(P, d, part)
        points += p
    if multi_start:
        best, (best_obj, best_feas) = d, P.objective_of(d)
        for seed in _seeds(P):
            sd = seed
            for part in P.partitions(sd.cuts):
                sd, p = descend(P, sd, part)
                points += p
            obj, feas = P.objective_of(sd)
            points += 1
            if feas and (not best_feas or obj < best_obj):
                best, best_obj, best_feas = sd, obj, True
        d = best

    changed, sweeps = True, 0
    while changed and sweeps < 8:
        sweeps += 1
        changed = False
        pi = 0
        while True:
            parts = P.partitions(d.cuts)
            if pi >= len(parts) or len(parts) == 1:
                break
            part = parts[pi]
            # under spmd a merge keeps every fold, so every merge is tried
            removable = []
            if pi < len(parts) - 1:
                removable.append(part[-1])
            if pi > 0:
                removable.append(part[0] - 1)
            base_obj = P.objective_of(d)[0]
            merged, merged_obj = None, None
            for cut in removable:
                d2 = d.with_cuts(c for c in d.cuts if c != cut)
                target = next(nodes for nodes in P.partitions(d2.cuts)
                              if part[0] in nodes)
                d2 = repair(P, P.tie(d2))
                d2, p = descend(P, d2, target)
                points += p
                obj, feas = P.objective_of(d2)
                points += 1
                if feas and (merged_obj is None or obj < merged_obj):
                    merged, merged_obj = d2, obj
            if merged is None or merged_obj > base_obj or (
                    merged_obj >= base_obj and len(merged.cuts) >= len(d.cuts)):
                pi += 1
                continue
            d = merged
            changed = True

    for _ in range(4):
        removed = False
        for cut in sorted(d.cuts):
            d2 = repair(P, P.tie(d.with_cuts(c for c in d.cuts if c != cut)))
            obj, feas = P.objective_of(d2)
            points += 1
            if feas and obj < P.objective_of(d)[0]:
                d, removed = d2, True
        if not removed:
            break
    for part in P.partitions(d.cuts):
        d, p = descend(P, d, part)
        points += p
    return d, points


# ----------------------------------------------------------------------
# brute force
# ----------------------------------------------------------------------

def _slots(P: Problem) -> List[Tuple[int, str, List[int]]]:
    """Independent decision slots of the uncut graph: (node, var, members)."""
    slots, seen = [], set()
    for i in range(P.n):
        for var in VARS:
            members = tuple(P.scope(i, ()))
            if (members, var) in seen:
                continue
            seen.add((members, var))
            slots.append((i, var, list(members)))
    return slots


def brute_force(P: Problem, max_points: Optional[int] = None,
                include_cuts: bool = False, chunk: int = 16384,
                **_engine_args) -> Tuple[Design, int]:
    """``_engine_args`` (chunk size, device count) do not change the result."""
    if include_cuts:
        raise NotImplementedError("the reference enumerates uncut designs")
    slots = _slots(P)
    menus = [P.menus[var][i] for i, var, _ in slots]
    sizes = [len(m) for m in menus]
    total = int(np.prod(sizes, dtype=object))
    count = total if max_points is None else min(total, max_points)
    # fold value each node takes for each digit of each slot
    clamp = []
    for (i, var, members), menu in zip(slots, menus):
        tab = {}
        for j in members:
            dim = int(P.dims[var][j])
            vals = []
            for v in menu:
                while v > 1 and dim % v:
                    v -= 1
                vals.append(v)
            tab[j] = np.array(vals, np.int64)
        clamp.append(tab)
    strides = [1] * len(slots)
    for s in range(len(slots) - 2, -1, -1):
        strides[s] = strides[s + 1] * sizes[s + 1]
    best_obj, best_row = np.inf, None
    for lo in range(0, count, chunk):
        idx = np.arange(lo, min(lo + chunk, count), dtype=np.int64)
        folds = {v: np.ones((len(idx), P.n), np.int64) for v in VARS}
        for s, (i, var, members) in enumerate(slots):
            digit = (idx // strides[s]) % sizes[s]
            for j in members:
                folds[var][:, j] = clamp[s][j][digit]
        _tie_rows(P, folds)
        s = P.evaluate(folds["s_in"], folds["s_out"], folds["kern"],
                       np.zeros((len(idx), P.n - 1), bool))
        obj = np.where(s.feasible, s.objective, np.inf)
        r = int(np.argmin(obj))
        if obj[r] < best_obj:
            best_obj = float(obj[r])
            best_row = tuple(tuple(int(x) for x in folds[v][r]) for v in VARS)
    if best_row is None:
        return P.initial(), count
    return Design((), *best_row), count


def _tie_rows(P: Problem, folds: Dict[str, np.ndarray]) -> None:
    """``Problem.tie`` for many uncut designs at once (in place)."""
    for members in P.groups:
        first = members[0]
        for var in VARS:
            folds[var][:, members] = folds[var][:, [first]]
    elem = P.kinds["elementwise"]
    folds["s_out"][:, elem] = folds["s_in"][:, elem]


SEARCHES = {"rule_based": rule_based, "brute_force": brute_force}
