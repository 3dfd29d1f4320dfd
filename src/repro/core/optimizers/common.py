"""Shared optimiser utilities: result container and feasibility repair."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from itertools import compress, count
from operator import ne
from typing import List, Optional, Tuple

from repro.core.hdgraph import Variables, boundary_bytes, partitions_from_cuts
from repro.core.objectives import Evaluation, Problem
from repro.core.perfmodel import partition_time
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace


@dataclass
class OptimResult:
    variables: Variables
    evaluation: Evaluation
    points: int                 # design points evaluated
    seconds: float
    history: List[Tuple[int, float]] = field(default_factory=list)
    name: str = ""

    @property
    def points_per_second(self) -> float:
        return self.points / self.seconds if self.seconds > 0 else float("inf")


def incumbent_better(cand_feasible: bool, cand_objective: float,
                     best_feasible: bool, best_objective: float) -> bool:
    """Feasibility-aware incumbent rule: a feasible candidate always beats an
    infeasible incumbent; among equally-feasible designs, lower O(V) wins.
    (An optimiser must never return an infeasible design when a feasible
    point was evaluated.)"""
    if cand_feasible and not best_feasible:
        return True
    if cand_feasible != best_feasible:
        return False
    return cand_objective < best_objective


@_trace.traced("optim.repair")
def repair(problem: Problem, v: Variables, max_steps: int = 1024) -> Variables:
    """Greedy feasibility repair.

    The paper assumes V_init (all folds 1, fully split) is feasible; on TPU a
    single over-HBM node (e.g. a 384-expert MoE layer, or an embedding table
    with its optimiser state, on one chip) can violate Eq. 6 even fully
    split. Folding *reduces* per-chip residency (s_O shards weights, s_I/k
    shard the activation stash), so we walk the worst partition's folds
    upward, accepting any move that strictly shrinks its residency; when no
    fold helps, split the partition.

    A fold move keeps the cuts and changes the triples of a few nodes (its
    scope and what ``propagate`` re-ties), so each candidate is scored on
    those nodes alone (``_RepairState``): the answer is the one a full
    ``check`` and ``evaluate`` of every candidate gives. While the design
    is a ``propagate`` fixed point, each candidate is also built on those
    nodes alone (``Backend.set_fold_local``). Every candidate counts in
    ``optim.repair.candidates``, and those built locally also in
    ``optim.repair.local_candidates``.
    """
    rep = problem.check(v)
    if rep.ok:
        return v
    state = _RepairState(problem, v, rep)
    try:
        return state.run(max_steps)
    finally:
        _metrics.counter("optim.repair.candidates").inc(state.candidates)
        _metrics.counter("optim.repair.local_candidates").inc(state.local)


class _RepairState:
    """One ``repair`` call's design and what ``check_all`` says of it, kept
    per node, edge, scan group and partition, so that a fold move is
    rescored where it changed the design: the nodes whose triple moved,
    the edges beside them, the scan groups and partitions holding them.
    Partitions are contiguous node ranges.

    - ``res[p]`` and ``pviol[p]``: partition ``p``'s summed
      ``hbm_resident`` (summed in ``check_resource``'s order, so that
      comparisons are bit-identical) and its resource (Eq. 6) and
      bandwidth (Eq. 7) violations;
    - ``nviol[i]``: node i's channel-factor (Eq. 8) and intra-matching
      (Eq. 9) violations; ``eviol[e]``: edge e's inter matching (Eq. 10);
      ``ties[(first node of a partition, group)]``: scan tying;
    - ``struct``: the sum of those and of the cuts' own checks, i.e. every
      violation not a partition's, which no accepted move may raise;
    - ``fixed``: whether the design is a ``propagate`` fixed point, so
      that a candidate can be built by ``set_fold_local``.
    """

    def __init__(self, problem: Problem, v: Variables, rep) -> None:
        self.problem = problem
        self.graph, self.backend = problem.graph, problem.backend
        self.platform = problem.platform
        self.candidates = self.local = 0
        self.v = v
        self.closed = self.backend.keeps_fixed_points(self.graph)
        self.fixed = self._is_fixed(v)
        self.evals = problem._eval_nodes(v)
        self.groups = self.graph.scan_members() if self.backend.scan_tying \
            else {}
        self._menus = {}
        self._infos = {}
        n = len(self.graph.nodes)
        self.parts = partitions_from_cuts(self.graph, v.cuts)
        self.part_of = [pi for pi, part in enumerate(self.parts)
                        for _ in part]
        self.res = [0.0] * len(self.parts)
        self.pviol = [0] * len(self.parts)
        for pi in range(len(self.parts)):
            self._refresh(pi)
        self.nviol = [self._info(i, v)[0] for i in range(n)]
        self.eviol = [self._edge_viol(e, v) for e in range(n - 1)]
        self.ties = {}
        for part in self.parts:
            self._count_ties(part)
        allowed = set(self.graph.cut_edges)
        self.struct = (sum(1 for c in v.cuts if c not in allowed)
                       + sum(self.nviol) + sum(self.eviol)
                       + sum(self.ties.values()))
        self.base = sum(1 for msg in rep.violations
                        if not msg.startswith("partition"))
        if self.struct != self.base or \
                sum(self.pviol) != len(rep.violations) - self.base:
            raise RuntimeError("repair: partition-local state disagrees "
                               "with check_all")

    def _is_fixed(self, v: Variables) -> bool:
        return self.backend.propagate(self.graph, v) == v

    @property
    def feasible(self) -> bool:
        return self.struct == 0 and not any(self.pviol)

    def _refresh(self, pi: int) -> None:
        """Residency, resource and bandwidth of partition ``pi``, as
        ``check_resource`` and ``check_bandwidth`` compute them."""
        part, evals, plat = self.parts[pi], self.evals, self.platform
        self.res[pi] = per_chip = sum(evals[i].hbm_resident for i in part)
        viol = 0
        multi = len(self.parts) > 1
        if multi:
            (d_in, d_out), = boundary_bytes(self.graph, [part])
            per_chip += (d_in + d_out) / plat.chips
        if per_chip > plat.hbm_bytes:
            viol += 1
        exec_model = self.problem.exec_model
        if exec_model == "streaming" and \
                sum(evals[i].chips for i in part) > plat.chips:
            viol += 1
        if multi:
            t = partition_time(self.graph, part, evals, exec_model)
            if t > 0 and (d_in + d_out) / t > plat.hbm_bw * plat.chips:
                viol += 1
        self.pviol[pi] = viol

    # -- the structural checks check_all enables --------------------------
    def _info(self, i: int, v: Variables):
        """(channel-factor and intra-matching violations, hbm_resident) of
        node i with its folds in ``v``, memoised per fold triple."""
        key = (i, v.s_in[i], v.s_out[i], v.kern[i])
        info = self._infos.get(key)
        if info is None:
            _, si, so, k = key
            n, b = self.graph.nodes[i], self.backend
            c = (n.rows % si != 0) + (n.col_div % so != 0) + \
                (n.batch % k != 0)
            c += bool(b.strict_kv and n.kv_limit and so > n.kv_limit)
            c += not self.platform.folds_realizable((si, so, k))
            c += bool(b.intra_matching and n.elementwise and si != so)
            hbm = self.problem._eval_nodes(v, (i,))[0].hbm_resident
            info = self._infos[key] = (c, hbm)
        return info

    def _edge_viol(self, e: int, v: Variables) -> int:
        """Inter matching (Eq. 10) of edge ``e``, inside a partition."""
        if not self.backend.inter_matching or \
                self.part_of[e] != self.part_of[e + 1]:
            return 0
        nodes = self.graph.nodes
        a = 1 if nodes[e].internal_rows else v.s_in[e]
        b = 1 if nodes[e + 1].internal_rows else v.s_in[e + 1]
        return int(a != b or v.kern[e] != v.kern[e + 1])

    def _tie_viol(self, start: int, g: int, v: Variables) -> int:
        """Scan tying of group ``g`` in the partition starting at node
        ``start``: each member whose triple differs from the first's."""
        part = self.parts[self.part_of[start]]
        members = [i for i in self.groups[g] if start <= i <= part[-1]]
        s_in, s_out, kern = v.s_in, v.s_out, v.kern
        f = members[0]
        first = (s_in[f], s_out[f], kern[f])
        return sum((s_in[i], s_out[i], kern[i]) != first for i in members)

    def _count_ties(self, part: List[int]) -> None:
        nodes = self.graph.nodes
        for g in {nodes[i].scan_group for i in part} & self.groups.keys():
            self.ties[(part[0], g)] = self._tie_viol(part[0], g, self.v)

    def _touched(self, moved: List[int]):
        """The edges (inter-matching backends) and (partition, scan
        group) pairs beside or holding the nodes ``moved``."""
        edges = ()
        if self.backend.inter_matching:
            last = len(self.graph.nodes) - 1
            edges = {e for j in moved for e in (j - 1, j) if 0 <= e < last}
        nodes, parts, part_of = self.graph.nodes, self.parts, self.part_of
        ties = {(parts[part_of[j]][0], nodes[j].scan_group) for j in moved}
        return edges, [t for t in ties if t[1] in self.groups]

    def _structural(self, v2: Variables, moved: List[int],
                    dnode: int) -> Optional[int]:
        """``struct`` of the design ``v2``, whose moved nodes change the
        node violations by ``dnode``; None where it exceeds ``base``."""
        s = self.struct + dnode
        edges, ties = self._touched(moved)
        old_edges = sum(self.eviol[e] for e in edges)
        old_ties = sum(self.ties[t] for t in ties)
        if s - old_edges - old_ties > self.base:
            return None              # exceeds it whatever edges and ties do
        s += sum(self._edge_viol(e, v2) for e in edges) - old_edges
        s += sum(self._tie_viol(st, g, v2) for st, g in ties) - old_ties
        return s if s <= self.base else None

    # -- the search -------------------------------------------------------
    def _moved(self, v2: Variables) -> List[int]:
        """Nodes whose fold triple differs between the design and ``v2``."""
        v, moved = self.v, set()
        for a, b in ((v.s_in, v2.s_in), (v.s_out, v2.s_out),
                     (v.kern, v2.kern)):
            if a != b:
                moved.update(compress(count(), map(ne, a, b)))
        return sorted(moved)

    def _menu(self, i: int, var: str) -> List[int]:
        m = self._menus.get((i, var))
        if m is None:
            m = self._menus[(i, var)] = self.backend.candidates(
                self.graph, i, var, self.platform)
        return m

    def _best_fold(self, worst: List[int], worst_res: float):
        """The first fattest node with a fold move that shrinks the worst
        partition: of its moves, the first of least residency that raises
        no structural violation, as (Variables, moved, struct); or None."""
        v, graph, backend = self.v, self.graph, self.backend
        infos, nviol = self._infos, self.nviol
        lo, hi = worst[0], worst[-1]
        hbm = [self.evals[i].hbm_resident for i in worst]
        for i in sorted(worst, key=lambda i: -hbm[i - lo]):
            shrink = []                  # (residency, Variables, moved, dnode)
            for var in ("s_out", "kern", "s_in"):
                cur = getattr(v, var)[i]
                higher = [c for c in self._menu(i, var) if c > cur]
                if not higher:
                    continue
                self.candidates += 1
                if self.fixed:
                    v2, moved = backend.set_fold_local(graph, v, i, var,
                                                       higher[0])
                    self.local += 1
                else:
                    v2 = backend.set_fold(graph, v, i, var, higher[0])
                    moved = self._moved(v2)
                s_in, s_out, kern = v2.s_in, v2.s_out, v2.kern
                vals, inside, dnode = hbm[:], False, 0
                for j in moved:
                    info = infos.get((j, s_in[j], s_out[j], kern[j])) or \
                        self._info(j, v2)
                    dnode += info[0] - nviol[j]
                    if lo <= j <= hi:
                        vals[j - lo] = info[1]
                        inside = True
                if not inside:
                    continue             # the worst partition is unchanged
                r2 = sum(vals)
                if r2 < worst_res - 1e-9:
                    shrink.append((r2, v2, moved, dnode))
            shrink.sort(key=lambda c: c[0])          # stable: ties keep order
            for _, v2, moved, dnode in shrink:
                s2 = self._structural(v2, moved, dnode)
                if s2 is not None:       # keeps realisability/matching
                    return v2, moved, s2
        return None

    def _accept(self, v2: Variables, moved: List[int], struct: int) -> None:
        edges, ties = self._touched(moved)
        self.v, self.struct = v2, struct
        # a set_fold answer: a fixed point again where the backend keeps
        # them and the move started from one, else ask propagate
        self.fixed = (self.fixed and self.closed) or self._is_fixed(v2)
        for j, e in zip(moved, self.problem._eval_nodes(v2, moved)):
            self.evals[j] = e
            self.nviol[j] = self._info(j, v2)[0]
        for e in edges:
            self.eviol[e] = self._edge_viol(e, v2)
        for st, g in ties:
            self.ties[(st, g)] = self._tie_viol(st, g, v2)
        for pi in sorted({self.part_of[j] for j in moved}):
            self._refresh(pi)

    def run(self, max_steps: int) -> Variables:
        for _ in range(max_steps):
            if self.feasible:
                break
            wi = max(range(len(self.res)), key=self.res.__getitem__)
            worst = self.parts[wi]
            best = self._best_fold(worst, self.res[wi])
            if best is not None:
                self._accept(*best)
                continue
            # no fold helps: split the worst partition at its midpoint
            edges = [e for e in self.graph.cut_edges if e not in self.v.cuts]
            inner = [e for e in edges if worst[0] <= e < worst[-1]]
            if not inner:
                break                    # single node over capacity: give up
            self._split(wi, inner[len(inner) // 2])
        return self._confirmed()

    def _split(self, wi: int, e: int) -> None:
        """Cut the worst partition at edge ``e``: only its halves change."""
        worst = self.parts[wi]
        halves = [worst[:e + 1 - worst[0]], worst[e + 1 - worst[0]:]]
        self.struct -= self.eviol[e]
        self.eviol[e] = 0
        for key in [k for k in self.ties if k[0] == worst[0]]:
            self.struct -= self.ties.pop(key)
        self.v = self.v.with_cuts(tuple(sorted(set(self.v.cuts) | {e})))
        if self.backend.inter_matching or not self.fixed:
            # a cut moves the inter-matching anchors; a tie that held in
            # the partition holds in its halves
            self.fixed = self._is_fixed(self.v)
        self.parts[wi:wi + 1] = halves
        self.res.insert(wi + 1, 0.0)
        self.pviol.insert(wi + 1, 0)
        for pi in range(wi + 1, len(self.parts)):
            for i in self.parts[pi]:
                self.part_of[i] = pi
        for pi, half in enumerate(halves, start=wi):
            self._refresh(pi)
            self._count_ties(half)
            self.struct += sum(self.ties[k] for k in self.ties
                               if k[0] == half[0])

    def _confirmed(self) -> Variables:
        """The design, once ``check_all`` agrees with the state about it
        (which also leaves the memo warm for the caller's evaluation)."""
        if self.problem.check(self.v).ok != self.feasible:
            raise RuntimeError("repair: partition-local state disagrees "
                               "with check_all")
        return self.v
