"""Batched design-space evaluation engine (the numpy engine).

The optimisers' wall-clock is dominated by ``Problem.evaluate`` — scalar
Python over dataclasses, one candidate at a time. ``BatchedEvaluator``
lowers an ``HDGraph`` + ``Platform`` + ``ModelOptions`` ONCE into flat
per-node arrays (flops, weight/act/inner/state/kv/carry bytes, kind index
sets, scan pairs) and evaluates a *batch* of candidate designs
``(s_in, s_out, kern)[N, nodes]`` plus a cut bitmask ``[N, edges]`` by
running the array program of ``core/array_program.py`` on numpy, over the
float64 record ``core/accel/lowering.host_arrays`` derives from those
arrays. The jax engine (``core/accel/``) runs the same program on the
device record, so the cost model has one batched source.

The scalar path (core/perfmodel.py + core/objectives.py) stays the reference
implementation; tests/test_batched_eval.py asserts batched == scalar within
1e-9 on objective, feasibility, partition times and residency. All arrays are
float64 and the per-element operation order mirrors the scalar code, so the
agreement is near-bit-exact (only reduction orders differ).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.core.accel import lowering
from repro.core.array_program import eval_core
from repro.core.hdgraph import HDGraph, Variables
from repro.core.perfmodel import ModelOptions
from repro.core.platform import Platform

_ATTN_KINDS = ("attn", "cross_attn", "enc_attn")

#: the platform-derived scalars the accel lowering consumes as per-problem
#: DEVICE DATA (core/accel/lowering.py), in ``platform_scalars()`` order.
#: Everything a candidate evaluation needs from the platform beyond the
#: fold-value menu reduces to this vector plus the realisability cube —
#: which is what lets one jitted executable serve any platform.
PLATFORM_SCALAR_FIELDS = ("peak_flops", "hbm_bw", "hbm_bytes", "ici_bw",
                          "dma_bw", "reconf_fixed_s", "chips")


@dataclass
class BatchResult:
    """Vectorised analogue of ``objectives.Evaluation`` for N candidates."""

    objective: np.ndarray          # [N] O(V), lower is better (Eq. 5)
    feasible: np.ndarray           # [N] bool
    latency: np.ndarray            # [N] Eq. 3
    throughput: np.ndarray         # [N] positive items/s (Eq. 4 un-negated)
    part_times: np.ndarray         # [N, nodes] T(P_i); entries >= nparts are 0
    nparts: np.ndarray             # [N] number of partitions
    reconf_time: np.ndarray        # [N] |C| * t_conf
    node_resident: np.ndarray      # [N, nodes] per-chip Eq. 6 residency
    node_times: np.ndarray         # [N, nodes] roofline node latency
    node_collective: np.ndarray = None  # [N, nodes] per-chip collective bytes

    def __len__(self) -> int:
        return int(self.objective.shape[0])


class NumpySegments:
    """The numpy engine's reductions: ``np.add.at`` / ``np.maximum.at`` into
    flat (row, partition) slots, whose cost is linear in the batch (a dense
    one-hot would be an [N, n, n] float64 array on the host). Row sums are
    sequential too, so a one-partition batch gives the same bits through
    either path of ``eval_core``."""

    @staticmethod
    def reducers(pid, iota_n, fdt):
        N, n = pid.shape
        flat = np.arange(N)[:, None] * n + pid

        def scatter(ufunc, init, idx, vals):
            out = np.full(N * n, init, fdt)
            ufunc.at(out, idx.ravel(), vals.ravel())
            return out.reshape(N, n)

        return (lambda v: scatter(np.add, 0.0, flat, v),
                lambda v: scatter(np.maximum, -np.inf, flat, v),
                lambda v: scatter(np.add, 0.0, flat[:, :-1], v))

    @staticmethod
    def row_sum(x):
        return np.add.accumulate(x, axis=1)[:, -1]

    @staticmethod
    def first_column(t0, n):
        out = np.zeros((t0.shape[0], n), t0.dtype)
        out[:, 0] = t0
        return out


class BatchedEvaluator:
    """One-time lowering of (graph, platform, backend rules, objective) into
    flat arrays + a vectorised ``evaluate_batch``."""

    def __init__(self, graph: HDGraph, platform: Platform, *,
                 strict_kv: bool, intra_matching: bool, inter_matching: bool,
                 scan_tying: bool, objective: str = "throughput",
                 exec_model: str = "streaming", batch_amortisation: int = 256,
                 opts: ModelOptions = ModelOptions()):
        self.graph = graph
        self.platform = platform
        self.strict_kv = strict_kv
        self.intra_matching = intra_matching
        self.inter_matching = inter_matching
        self.scan_tying = scan_tying
        self.objective = objective
        self.exec_model = exec_model
        self.batch_amortisation = batch_amortisation
        self.opts = opts
        self.mode = graph.mode
        self._real_memo: Dict[Tuple[int, int, int], bool] = {}
        self._lower()

    @classmethod
    def from_problem(cls, problem) -> "BatchedEvaluator":
        b = problem.backend
        return cls(problem.graph, problem.platform,
                   strict_kv=b.strict_kv, intra_matching=b.intra_matching,
                   inter_matching=b.inter_matching, scan_tying=b.scan_tying,
                   objective=problem.objective, exec_model=problem.exec_model,
                   batch_amortisation=problem.batch_amortisation,
                   opts=problem.opts)

    # ------------------------------------------------------------------
    # lowering
    # ------------------------------------------------------------------
    def _lower(self) -> None:
        nodes = self.graph.nodes
        n = len(nodes)
        self.n_nodes = n
        f = lambda attr: np.array([getattr(x, attr) for x in nodes], np.float64)
        i = lambda attr: np.array([getattr(x, attr) for x in nodes], np.int64)
        m = lambda attr: np.array([bool(getattr(x, attr)) for x in nodes])

        self.flops = f("flops")
        self.weight_bytes = f("weight_bytes")
        self.act_bytes = f("act_bytes")
        self.inner_bytes = f("inner_bytes")
        self.state_bytes = f("state_bytes")
        self.kv_bytes = f("kv_bytes")
        self.carry_bytes = f("carry_bytes")
        self.batch = i("batch")
        self.rows = i("rows")
        self.cols = i("cols")
        self.fm_width = i("fm_width")
        self.col_div = np.array([x.col_div for x in nodes], np.int64)
        self.kv_limit = i("kv_limit")
        self.latent_dim = i("latent_dim")
        self.ep_topk = i("ep_topk")
        self.scan_group = i("scan_group")

        self.internal = m("internal_rows")
        self.elementwise = m("elementwise")
        self.weight_stream = m("weight_stream")

        allowed = np.zeros(max(n - 1, 0), bool)
        for e in self.graph.cut_edges:
            allowed[e] = True
        self.cut_allowed = allowed

        # kind column index sets: the sources of the array program's kind
        # masks (lowering.host_arrays)
        w = lambda mask: np.nonzero(mask)[0]
        kind = lambda k: w([x.kind in k for x in nodes])
        coll = lambda k: w([x.collective_kind == k for x in nodes])
        self.i_attn = kind(_ATTN_KINDS)
        self.i_head = kind(("head",))
        self.i_tp = coll("tp_allreduce")
        self.i_ep = coll("ep_alltoall")
        self.i_vocab = coll("vocab_allreduce")
        self.i_vhead = coll("vocab_head")
        self.i_kv = w(~self.internal & (self.kv_bytes > 0))
        self.i_carry = w(~self.internal & (self.kv_bytes == 0)
                         & (self.carry_bytes > 0))
        self.i_latent = w(self.latent_dim > 0)

        # Boundary featuremap bytes (Eq. 7 convention: full rows, bf16).
        self.node_d = (self.batch * self.rows * self.fm_width).astype(
            np.float64) * 2.0
        # Resharding all-gather bytes when edge layouts mismatch (spmd
        # backend): full featuremap of the upstream node at its mode rows.
        r_rows = np.where(self.internal, 1,
                          1 if self.mode == "decode" else self.rows)
        self.reshard_full = (self.batch * r_rows * self.fm_width).astype(
            np.float64) * 2.0

        # scan-group consecutive member pairs (pid is monotone along the
        # chain, so same-partition members of a group are consecutive in its
        # ordered member list — pairwise equality is a complete check).
        pairs = []
        by_group: Dict[int, List[int]] = {}
        for j, g in enumerate(self.scan_group.tolist()):
            if g >= 0:
                by_group.setdefault(g, []).append(j)
        for members in by_group.values():
            pairs.extend(zip(members[:-1], members[1:]))
        self.scan_pairs = np.array(pairs, np.int64).reshape(-1, 2)

    # ------------------------------------------------------------------
    def platform_scalars(self) -> np.ndarray:
        """The platform scalar vector, ``PLATFORM_SCALAR_FIELDS`` order.

        float64 [7]; ``chips`` is float (exact for any real mesh). The jax
        lowering turns each entry into a scalar device array so platform
        identity never enters the traced program.
        """
        p = self.platform
        return np.array([float(getattr(p, f)) for f in
                         PLATFORM_SCALAR_FIELDS], np.float64)

    # ------------------------------------------------------------------
    # packing helpers
    # ------------------------------------------------------------------
    def pack(self, designs: Sequence[Variables]
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        n, N = self.n_nodes, len(designs)
        si = np.empty((N, n), np.int64)
        so = np.empty((N, n), np.int64)
        kk = np.empty((N, n), np.int64)
        cb = np.zeros((N, max(n - 1, 0)), bool)
        for r, v in enumerate(designs):
            si[r] = v.s_in
            so[r] = v.s_out
            kk[r] = v.kern
            for c in v.cuts:
                cb[r, c] = True
        return si, so, kk, cb

    def unpack_row(self, si, so, kk, cb, row: int) -> Variables:
        cuts = tuple(int(e) for e in np.nonzero(cb[row])[0])
        return Variables(cuts, tuple(int(x) for x in si[row]),
                         tuple(int(x) for x in so[row]),
                         tuple(int(x) for x in kk[row]))

    # ------------------------------------------------------------------
    # the array program's inputs (built after ``_lower``, on first use)
    # ------------------------------------------------------------------
    @functools.cached_property
    def arrays(self) -> "lowering.DeviceArrays":
        """The float64/int64 host record of the array program."""
        return lowering.host_arrays(self)

    @functools.cached_property
    def static(self) -> "lowering.StaticSpec":
        return lowering.build_static_spec(self)

    def _realizable(self, si, so, kk) -> np.ndarray:
        """Mesh realisability over unique fold triples (memoised): the
        test for fold menus too large for a realisability cube."""
        enc = (si.astype(np.int64) << 40) | (so << 20) | kk
        uniq, inv = np.unique(enc, return_inverse=True)
        ok = np.empty(len(uniq), bool)
        memo = self._real_memo
        for u, e in enumerate(uniq.tolist()):
            t = (e >> 40, (e >> 20) & 0xFFFFF, e & 0xFFFFF)
            r = memo.get(t)
            if r is None:
                r = self.platform.folds_realizable(t)
                memo[t] = r
            ok[u] = r
        return ok[inv].reshape(si.shape)

    def evaluate_batch(self, s_in, s_out, kern, cuts) -> BatchResult:
        """Evaluate N candidates. ``s_in/s_out/kern``: int arrays [N, nodes];
        ``cuts``: bool bitmask [N, nodes-1] over chain edges."""
        si = np.asarray(s_in, np.int64)
        so = np.asarray(s_out, np.int64)
        kk = np.asarray(kern, np.int64)
        cb = np.asarray(cuts, bool)
        N, n = si.shape
        if n != self.n_nodes or so.shape != si.shape or kk.shape != si.shape \
                or cb.shape != (N, max(n - 1, 0)):
            raise ValueError(
                f"expected fold arrays [N, {self.n_nodes}] and cut mask "
                f"[N, {self.n_nodes - 1}]; got s_in {si.shape}, s_out "
                f"{so.shape}, kern {kk.shape}, cuts {cb.shape}")
        A = self.arrays
        out = eval_core(
            self.static, A, si, so, kk, cb, single_partition=not cb.any(),
            xp=np, seg=NumpySegments,
            realizable=None if A.real_table is not None else self._realizable)
        return BatchResult(
            objective=out["objective"], feasible=out["feasible"],
            latency=out["latency"], throughput=out["throughput"],
            part_times=out["part_times"], nparts=out["nparts"],
            reconf_time=out["reconf_time"],
            node_resident=out["node_resident"],
            node_times=out["node_times"],
            node_collective=out["node_collective"])


# ----------------------------------------------------------------------
# Multi-network co-mapping mirror (docs/comapping.md)
# ----------------------------------------------------------------------

@dataclass
class CoMapBatchResult:
    """Vectorised analogue of ``objectives.CoMapEvaluation`` for N joint
    candidates under ONE split."""

    objective: np.ndarray            # [N] composite, lower is better
    feasible: np.ndarray             # [N] bool (budget mask applied)
    budget_ok: bool                  # the split's shared-budget mask bit
    per_net: List[BatchResult]       # one BatchResult per net

    def __len__(self) -> int:
        return int(self.objective.shape[0])


class CoMapBatchedEvaluator:
    """Vectorised host mirror of ``CoMapProblem.evaluate``.

    The N nets' node arrays conceptually concatenate along one node axis
    — ``seg_ids``/``offsets`` map positions to nets, which is how joint
    fold/cut vectors address the combined graph — and every net's slice
    of a joint candidate evaluates through that net's per-sub-problem
    array program. The shared chip budget enters as an explicit per-split
    constraint mask (``budget_mask``) applied INSIDE the candidate:
    a candidate on an over-budget split is infeasible no matter how good
    its per-net designs are. The composite combine is the same float64
    host arithmetic as the scalar reference (``combine_composite``), so
    per-net agreement at 1e-9 implies joint agreement at 1e-9.
    """

    def __init__(self, cp) -> None:
        self.cp = cp
        counts = [len(g.nodes) for g in cp.graphs]
        #: net index of every position on the concatenated node axis
        self.seg_ids = np.repeat(np.arange(len(counts)), counts)
        #: net i's nodes live at [offsets[i], offsets[i+1])
        self.offsets = np.concatenate(([0], np.cumsum(counts)))
        self.n_nodes = int(self.offsets[-1])
        self._bevs: Dict[Tuple[int, int], BatchedEvaluator] = {}

    def evaluator(self, split_index: int, net: int) -> BatchedEvaluator:
        """The (split, net) sub-problem's array program (memoised)."""
        key = (split_index, net)
        bev = self._bevs.get(key)
        if bev is None:
            bev = self.cp.subproblem(split_index, net).batched()
            self._bevs[key] = bev
        return bev

    def budget_mask(self) -> np.ndarray:
        """[S] bool: splits whose per-net chip allocations fit the shared
        budget. True for the whole generated menu by construction;
        user-supplied menus may carry False entries."""
        return np.array(
            [not self.cp.budget_violations(s)
             for s in range(len(self.cp.resolved_splits()))], bool)

    def split_variables(self, joint: "Variables") -> List[Variables]:
        """Slice ONE joint design (folds/cuts over the concatenated node
        axis; cut indices on the joint edge numbering) back into per-net
        ``Variables`` — the segment-id decode of a joint candidate."""
        if len(joint.s_in) != self.n_nodes:
            raise ValueError(f"joint design has {len(joint.s_in)} fold "
                             f"entries for a {self.n_nodes}-node axis")
        out = []
        for i in range(len(self.cp.graphs)):
            lo, hi = int(self.offsets[i]), int(self.offsets[i + 1])
            cuts = tuple(c - lo for c in joint.cuts
                         if lo <= c < hi - 1)
            out.append(Variables(cuts, joint.s_in[lo:hi],
                                 joint.s_out[lo:hi], joint.kern[lo:hi]))
        return out

    def join_variables(self, per_net: Sequence[Variables]) -> Variables:
        """Inverse of ``split_variables``: concatenate per-net designs
        onto the joint node axis (boundary edges between nets carry no
        cut — partitions never span nets)."""
        cuts, si, so, kk = [], [], [], []
        for i, v in enumerate(per_net):
            lo = int(self.offsets[i])
            cuts.extend(lo + c for c in v.cuts)
            si.extend(v.s_in)
            so.extend(v.s_out)
            kk.extend(v.kern)
        return Variables(tuple(cuts), tuple(si), tuple(so), tuple(kk))

    def evaluate_batch(self, split_index: int,
                       designs: Sequence[Sequence["Variables"]]
                       ) -> CoMapBatchResult:
        """Evaluate B joint candidates under one split.

        ``designs`` is a B-long sequence of N-long per-net design rows
        (use ``split_variables`` first for candidates expressed on the
        joint node axis). Returns float64 composites identical to the
        scalar reference at 1e-9.
        """
        cp = self.cp
        N = cp.n_nets
        rows = [tuple(row) for row in designs]
        if any(len(r) != N for r in rows):
            raise ValueError(f"every design row must carry {N} per-net "
                             f"designs")
        budget_ok = not cp.budget_violations(split_index)
        per_net: List[BatchResult] = []
        for i in range(N):
            bev = self.evaluator(split_index, i)
            res = bev.evaluate_batch(*bev.pack([r[i] for r in rows]))
            cp.subproblem(split_index, i).note_batch_evals(len(res))
            per_net.append(res)
        B = len(rows)
        weights = cp.net_weights
        feas = np.full(B, budget_ok, bool)
        for res in per_net:
            feas &= res.feasible.astype(bool)
        if cp.objective == "worst_latency":
            comp = np.max(np.stack([r.latency for r in per_net]), axis=0)
        else:
            thr = np.stack([w * r.throughput
                            for w, r in zip(weights, per_net)])
            comp = (-np.min(thr, axis=0)
                    if cp.objective == "maxmin_throughput"
                    else -np.sum(thr, axis=0))
        return CoMapBatchResult(objective=comp.astype(np.float64),
                                feasible=feas, budget_ok=budget_ok,
                                per_net=per_net)
