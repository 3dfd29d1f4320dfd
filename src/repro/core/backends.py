"""Backend integration (paper §IV-A, Tables I & II) — TPU edition.

Three lowering backends of decreasing design-space richness mirror the paper's
fpgaConvNet / FINN / HLS4ML triple:

  spmd      (≈fpgaConvNet)  all three folds free per scan group; adjacent
                            layout mismatches are ALLOWED and pay a modelled
                            resharding collective (inter matching ✗).
  megatron  (≈FINN)         s_O free per scan group; s_I and k are global
                            (SIMD-like tying); inter matching ✓ (no resharding
                            collectives may be inserted); strict KV channel
                            factor (s_O must divide kv_heads on attention).
  simple    (≈HLS4ML)       one global reuse factor: pure data parallelism
                            (k global, s_I = s_O = 1). intra matching ✗.

Each backend provides the candidate fold menus, mutation moves with the
paper's constraint propagation ("the change is propagated throughout the
whole HD-graph to fix intra/inter folding matching"), and the brute-force
enumeration space.
"""
from __future__ import annotations

import itertools
import random
from bisect import bisect_left
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.core.hdgraph import HDGraph, Node, Variables, resource_minimal
from repro.core.platform import Platform

VARS = ("s_in", "s_out", "kern")


def _divisors_from(values: Iterable[int], dim: int) -> List[int]:
    return sorted(v for v in values if v >= 1 and dim % v == 0)


def _within(members: List[int], lo: int, hi: int) -> List[int]:
    """The ascending ``members`` in ``[lo, hi)``."""
    return members[bisect_left(members, lo):bisect_left(members, hi)]


@dataclass(frozen=True)
class Backend:
    name: str
    strict_kv: bool
    intra_matching: bool
    inter_matching: bool
    scan_tying: bool
    granularity: Dict[str, str]        # var -> node | group | global
    fixed_unity: Tuple[str, ...] = ()  # vars pinned to 1 (simple backend)

    # ------------------------------------------------------------------
    # candidate menus (channel-factor-legal, mesh-realisable fold values)
    # ------------------------------------------------------------------
    def candidates(self, graph: HDGraph, i: int, var: str,
                   platform: Platform) -> List[int]:
        node = graph.nodes[i]
        if var in self.fixed_unity:
            return [1]
        values = platform.fold_values()
        if var == "s_in":
            if self.granularity["s_in"] == "global":
                return self._global_row_candidates(graph, platform)
            return _divisors_from(values, node.rows)
        if var == "s_out":
            dim = node.col_div
            cands = _divisors_from(values, dim)
            if self.strict_kv and node.kv_limit:
                cands = [c for c in cands if c <= node.kv_limit
                         and node.kv_limit % c == 0]
            return cands or [1]
        if var == "kern":
            return _divisors_from(values, node.batch)
        raise ValueError(var)

    def _global_row_candidates(self, graph: HDGraph,
                               platform: Platform) -> List[int]:
        cands = set(platform.fold_values())
        for n in graph.nodes:
            if n.internal_rows:
                continue
            cands &= set(_divisors_from(platform.fold_values(), n.rows))
        return sorted(cands) or [1]

    # ------------------------------------------------------------------
    # scoped assignment with constraint propagation
    #
    # Scopes are PARTITION-LOCAL: each partition is its own compiled
    # program (its own "bitstream"), so variable tying and layout matching
    # never cross a cut — reconfigurability is exactly what frees them
    # (paper §III-B).
    # ------------------------------------------------------------------
    @staticmethod
    def _partition_of(graph: HDGraph, i: int,
                      cuts: Sequence[int]) -> range:
        """The partition holding node i; ``cuts`` ascending."""
        k = bisect_left(cuts, i)
        lo = cuts[k - 1] + 1 if k else 0
        hi = cuts[k] + 1 if k < len(cuts) else len(graph.nodes)
        return range(lo, hi)

    def _scope_in(self, graph: HDGraph, i: int, var: str,
                  part: range) -> Sequence[int]:
        """``scope`` inside node i's partition ``part``."""
        g = self.granularity[var]
        if g == "global":
            return part
        sg = graph.nodes[i].scan_group
        if g == "group" and sg >= 0:
            return _within(graph.scan_members()[sg], part.start, part.stop)
        return [i]

    def scope(self, graph: HDGraph, i: int, var: str,
              cuts: Sequence[int] = ()) -> List[int]:
        """Node indices that share this variable with node i."""
        part = self._partition_of(graph, i, sorted(cuts))
        return list(self._scope_in(graph, i, var, part))

    def set_fold(self, graph: HDGraph, v: Variables, i: int, var: str,
                 value: int) -> Variables:
        """Set ``var`` of node i to ``value`` over its scope, each node
        clamped to a legal divisor, then ``propagate`` the whole design.

        Takes any design, a ``propagate`` fixed point or not: a design
        after a cut move (``random_move``), the brute force's slot
        assignments, the exporter's. A caller that holds a fixed point
        and rebuilds many moves from it uses ``set_fold_local``."""
        si, so, kk = list(v.s_in), list(v.s_out), list(v.kern)
        self._scatter(graph, {"s_in": si, "s_out": so, "kern": kk}[var],
                      self.scope(graph, i, var, v.cuts), var, value)
        out = Variables(v.cuts, tuple(si), tuple(so), tuple(kk))
        return self.propagate(graph, out)

    def set_fold_local(self, graph: HDGraph, v: Variables, i: int, var: str,
                       value: int) -> Tuple[Variables, List[int]]:
        """``set_fold`` of a ``propagate`` fixed point, rebuilt where the
        move reaches: ``(set_fold(graph, v, i, var, value), moved)``, with
        ``moved`` the sorted nodes whose fold triple differs from ``v``.

        Precondition: ``propagate(graph, v) == v``; on any other design
        the answer may differ from ``set_fold``'s. So its callers are
        those that know their design is a fixed point: ``repair`` while
        its design is one (it checks at the start, after a split, and
        after a move unless ``keeps_fixed_points``), and the rule-based
        seeds, built from the all-ones design, which is one. Matching
        never crosses a cut, and in a fixed point every tie already
        holds, so only the move's partition can change. Without inter
        matching, and with a scope narrower than the partition, only the
        scope and the in-partition members of the scan groups it touches
        can change, and only they are re-tied."""
        part = self._partition_of(graph, i, v.cuts)
        scope = self._scope_in(graph, i, var, part)
        si, so, kk = list(v.s_in), list(v.s_out), list(v.kern)
        self._scatter(graph, {"s_in": si, "s_out": so, "kern": kk}[var],
                      scope, var, value)
        sg = graph.nodes[i].scan_group
        if self.inter_matching or self.granularity[var] == "global":
            work = part
        elif self.scan_tying and sg >= 0:
            # a group-wide scope is the group; a node-wide one re-ties it
            work = _within(graph.scan_members()[sg], part.start, part.stop)
        else:
            work = scope
        self._propagate_part(graph, si, so, kk, work)
        s_in, s_out, kern = v.s_in, v.s_out, v.kern
        moved = [j for j in work if si[j] != s_in[j] or so[j] != s_out[j]
                 or kk[j] != kern[j]]
        return Variables(v.cuts, tuple(si), tuple(so), tuple(kk)), moved

    def keeps_fixed_points(self, graph: HDGraph) -> bool:
        """Whether ``set_fold`` maps a ``propagate`` fixed point to another
        on ``graph``: so it does where the members of each scan group
        agree on what ``propagate`` reads of them (the intra-matching
        flag; with inter matching also rows, batch and internal rows), as
        in every graph the builder makes (one group a node kind)."""
        if not self.scan_tying:
            return True
        keys = ("elementwise",)
        if self.inter_matching:
            keys += ("rows", "batch", "internal_rows")
        return graph.scan_groups_agree(keys)

    def _scatter(self, graph: HDGraph, arr: List[int], scope: Iterable[int],
                 var: str, value: int) -> None:
        """Write ``value`` to ``arr`` over ``scope``, clamped per node to
        the nearest divisor below it (propagation keeps V valid), one
        clamp per distinct dimension."""
        dims = graph.fold_dims()[var]
        nodes = graph.nodes
        keep_internal = var == "s_in" and self.granularity["s_in"] == "global"
        clamped: Dict[int, int] = {}
        for j in scope:
            if keep_internal and nodes[j].internal_rows:
                continue                     # decode split-KV keeps its own s_I
            dim = dims[j]
            val = clamped.get(dim)
            if val is None:
                val = value
                while val > 1 and dim % val != 0:
                    val -= 1
                clamped[dim] = val
            arr[j] = val

    def propagate(self, graph: HDGraph, v: Variables) -> Variables:
        """Fix intra (Eq. 9) and inter (Eq. 10) matching after a change.

        Matching is partition-local: across a cut, the featuremap is staged
        through HBM, so no layout agreement is required (the paper's data
        lines only wire blocks within one configuration)."""
        si, so, kk = list(v.s_in), list(v.s_out), list(v.kern)
        bounds = [0] + [c + 1 for c in sorted(v.cuts)] + [len(graph.nodes)]
        for lo, hi in zip(bounds, bounds[1:]):
            self._propagate_part(graph, si, so, kk, range(lo, hi))
        return Variables(v.cuts, tuple(si), tuple(so), tuple(kk))

    def _propagate_part(self, graph: HDGraph, si: List[int], so: List[int],
                        kk: List[int], part: Sequence[int]) -> None:
        """``propagate``'s body for the ascending nodes ``part`` of one
        partition, in place: the whole partition, or (on a backend without
        inter matching) the partition's members of whole scan groups and
        any ungrouped nodes, whose matching reads nothing else."""
        nodes = graph.nodes
        if self.scan_tying:
            # harmonise scan-group folds within the partition (one stacked
            # lax.scan has a single sharding): first member's triple wins.
            anchors = {}
            for j in part:
                g = nodes[j].scan_group
                if g < 0:
                    continue
                if g not in anchors:
                    anchors[g] = (si[j], so[j], kk[j])
                else:
                    si[j], so[j], kk[j] = anchors[g]
        if self.intra_matching:
            for j in part:
                if nodes[j].elementwise:
                    so[j] = si[j]
        if self.inter_matching:
            # chain equality on boundary layout => per-partition (s_I, k);
            # anchored at the partition's first non-internal node.
            anchor_si = next((si[j] for j in part
                              if not nodes[j].internal_rows), 1)
            anchor_k = kk[part[0]]
            for j in part:
                n = nodes[j]
                kk[j] = anchor_k if n.batch % anchor_k == 0 else 1
                if not n.internal_rows:
                    si[j] = anchor_si if n.rows % anchor_si == 0 else 1
                if n.elementwise and self.intra_matching:
                    so[j] = si[j]

    def initial(self, graph: HDGraph) -> Variables:
        return self.propagate(graph, resource_minimal(graph))

    # ------------------------------------------------------------------
    # SA random transformation (paper Algorithm 1, line 5)
    # ------------------------------------------------------------------
    def random_move(self, rng: random.Random, graph: HDGraph, v: Variables,
                    platform: Platform, allow_cuts: bool = True) -> Variables:
        n = len(graph.nodes)
        r = rng.random()
        if allow_cuts and r < 0.25:
            cuts = set(v.cuts)
            move = rng.random()
            all_edges = set(graph.cut_edges)
            if move < 0.45 and cuts:
                cuts.remove(rng.choice(sorted(cuts)))          # merge
            elif move < 0.9 and (all_edges - cuts):
                cuts.add(rng.choice(sorted(all_edges - cuts)))  # split
            elif cuts and (all_edges - cuts):
                cuts.remove(rng.choice(sorted(cuts)))
                cuts.add(rng.choice(sorted(all_edges - cuts)))  # move
            return v.with_cuts(sorted(cuts))
        i = rng.randrange(n)
        if r < 0.60:
            # joint re-draw of the node's whole fold triple. TPU adaptation:
            # mesh-realisable fold menus are far coarser than FPGA integer
            # folds, so single-variable moves cannot cross the valleys between
            # e.g. TP-heavy (16,16,1) and DP-heavy (1,1,256) states.
            menus = {var: self.candidates(graph, i, var, platform)
                     for var in VARS}
            for _ in range(8):
                triple = {var: rng.choice(menus[var]) for var in VARS}
                if platform.folds_realizable(tuple(triple.values())):
                    break
            out = v
            for var, val in triple.items():
                out = self.set_fold(graph, out, i, var, val)
            return out
        var = rng.choice([x for x in VARS if x not in self.fixed_unity] or ["kern"])
        cands = self.candidates(graph, i, var, platform)
        cur = getattr(v, {"s_in": "s_in", "s_out": "s_out", "kern": "kern"}[var])[i]
        choices = [c for c in cands if c != cur] or cands
        return self.set_fold(graph, v, i, var, rng.choice(choices))

    # ------------------------------------------------------------------
    # brute-force enumeration space (paper §IV-B / Table IV)
    # ------------------------------------------------------------------
    def space(self, graph: HDGraph, platform: Platform,
              include_cuts: bool = True):
        """Yield (scopes, menus): independent decision slots and their menus."""
        slots: List[Tuple[int, str]] = []
        seen = set()
        for i in range(len(graph.nodes)):
            for var in VARS:
                if var in self.fixed_unity:
                    continue
                key = (tuple(self.scope(graph, i, var)), var)
                if key in seen:
                    continue
                seen.add(key)
                slots.append((i, var))
        menus = [self.candidates(graph, i, var, platform) for i, var in slots]
        return slots, menus

    def design_space_size(self, graph: HDGraph, platform: Platform,
                          include_cuts: bool = True,
                          per_node: bool = True) -> float:
        """|V| — the paper's Table-IV quantity. ``per_node=True`` counts the
        raw per-node space (before tying), matching how the paper reports
        backend spaces; tying reduces the searched space."""
        size = 1.0
        if per_node:
            for i, node in enumerate(graph.nodes):
                for var in VARS:
                    if var in self.fixed_unity:
                        continue
                    size *= max(1, len(self.candidates(graph, i, var, platform)))
        else:
            slots, menus = self.space(graph, platform)
            for m in menus:
                size *= max(1, len(m))
        if include_cuts:
            size *= 2.0 ** (len(graph.nodes) - 1)
        return size


SPMD = Backend(
    name="spmd",
    strict_kv=False,
    intra_matching=True,
    inter_matching=False,
    scan_tying=True,
    granularity={"s_in": "group", "s_out": "group", "kern": "group"},
)

MEGATRON = Backend(
    name="megatron",
    strict_kv=True,
    intra_matching=True,
    inter_matching=True,
    scan_tying=True,
    granularity={"s_in": "global", "s_out": "group", "kern": "global"},
)

SIMPLE = Backend(
    name="simple",
    strict_kv=True,
    intra_matching=False,
    inter_matching=True,
    scan_tying=True,
    granularity={"s_in": "global", "s_out": "global", "kern": "global"},
    fixed_unity=("s_in", "s_out"),
)

BACKENDS = {b.name: b for b in (SPMD, MEGATRON, SIMPLE)}
