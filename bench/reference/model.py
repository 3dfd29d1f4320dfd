"""Plain reference of the mapping problem: graph, cost model, constraints.

A straightforward float64 implementation of the semantics the mapping
optimiser promises (the SAMO paper's §III cost model as this repository
states it for TPU pod slices): a model becomes a chain of nodes, a design
gives each node three folds (rows ``s_in``, channels ``s_out``, batch
``kern``) and cuts the chain into partitions, and a design is scored by
its latency or throughput and checked against the platform's limits.

It reads only the configuration and traffic files of the benchmark and
imports nothing of the program under test. It covers what the benchmark's
cells state, and refuses anything else: the ``spmd`` backend (folds tied
per scan group inside a partition, layout changes priced as resharding)
and execution model (a partition's time is the sum of its nodes'), the
``train`` and ``prefill`` modes, and the cost model's default switches
(no ZeRO-1, no sequence-parallel stash).

Every quantity is computed for a batch of designs at once (rows of int
arrays ``[N, n]``); ``dtype`` selects the arithmetic precision, so the
lower-precision control of the check is this same code at bfloat16.
Sums over nodes run in node order, one node at a time.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

BF16_BYTES = 2.0
FP32_BYTES = 4.0
VARS = ("s_in", "s_out", "kern")
#: scan groups: nodes of one kind inside one partition share their folds
SCAN_GROUP = {"attn": 0, "ssm": 1, "ffn": 2, "moe": 3}
ATTENTION_KINDS = ("attn",)


@dataclass(frozen=True)
class Node:
    name: str
    kind: str
    layer: int
    rows: int
    col_div: int
    batch: int
    flops: float
    weight_bytes: float
    act_bytes: float
    inner_bytes: float = 0.0
    state_bytes: float = 0.0
    kv_bytes: float = 0.0
    carry_bytes: float = 0.0
    kv_limit: int = 0
    ep_topk: int = 0
    elementwise: bool = False
    weight_stream: bool = False
    collective: str = "none"
    fm_width: int = 0

    @property
    def scan_group(self) -> int:
        return SCAN_GROUP.get(self.kind, -1)


# ----------------------------------------------------------------------
# the model as a chain of nodes
# ----------------------------------------------------------------------

def mixer_kind(model: dict, i: int) -> str:
    period = model.get("attn_layer_period", 1)
    if period > 1:
        return "attn" if i % period == model["attn_layer_offset"] else "ssm"
    return "attn"


def channel_kind(model: dict, i: int) -> str:
    if model.get("num_experts", 0) <= 1:
        return "ffn"
    period = model["expert_layer_period"]
    return "moe" if i % period == model["expert_layer_offset"] else "ffn"


def build_graph(model: dict, shape: dict) -> List[Node]:
    """The node chain of ``model`` (published sizes, HF key names) at
    ``shape`` (``seq_len``, ``global_batch``, ``mode``)."""
    mode = shape["mode"]
    if mode not in ("train", "prefill"):
        raise NotImplementedError(f"reference covers train/prefill, "
                                  f"not {mode!r}")
    B, S = shape["global_batch"], shape["seq_len"]
    tm = 3.0 if mode == "train" else 1.0
    stream = mode != "train"
    D, V = model["hidden_size"], model["vocab_size"]
    H, Hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = D // H
    F = model["intermediate_size"]
    mats = 3 if model["hidden_act"] == "silu" else 2
    act = 4.0 * B * S * D * BF16_BYTES

    nodes = [Node("embed", "embed", -1, S, V, B, flops=B * S * D,
                  weight_bytes=V * D * BF16_BYTES,
                  act_bytes=B * S * D * BF16_BYTES + B * S * 4.0,
                  collective="vocab_allreduce", fm_width=D)]
    for i in range(model["num_hidden_layers"]):
        if mixer_kind(model, i) == "attn":
            proj = 2.0 * B * S * D * (H * dh + 2 * Hkv * dh) \
                + 2.0 * B * S * H * dh * D
            sdpa = 2.0 * B * H * S * S * dh * 2.0 * 0.5       # causal
            kv = B * S * 2 * Hkv * dh * BF16_BYTES
            nodes.append(Node(
                f"l{i}.attn", "attn", i, S, H, B, flops=(proj + sdpa) * tm,
                weight_bytes=(2 * D * H * dh + 2 * D * Hkv * dh) * BF16_BYTES,
                act_bytes=act, inner_bytes=2.0 * B * S * H * dh * BF16_BYTES,
                state_bytes=0.0 if mode == "train" else kv, kv_bytes=kv,
                kv_limit=Hkv, weight_stream=stream,
                collective="tp_allreduce", fm_width=D))
        else:
            di = model["mamba_expand"] * D
            ds, dtr = model["mamba_d_state"], model["mamba_dt_rank"]
            conv = model["mamba_d_conv"]
            flops = (2.0 * B * S * D * 2 * di + 2.0 * B * S * di * (dtr + 2 * ds)
                     + 2.0 * B * S * dtr * di + 2.0 * B * S * di * conv
                     + 9.0 * B * S * di * ds + 2.0 * B * S * di * D)
            wb = (D * 2 * di + di * (dtr + 2 * ds) + dtr * di + di * conv
                  + di * ds + 2 * di + di * D) * BF16_BYTES
            state = B * di * ds * FP32_BYTES + B * di * conv * BF16_BYTES
            nodes.append(Node(
                f"l{i}.ssm", "ssm", i, S, di, B, flops=flops * tm,
                weight_bytes=wb, act_bytes=act,
                inner_bytes=3.0 * B * S * di * BF16_BYTES,
                state_bytes=0.0 if mode == "train" else state,
                carry_bytes=B * di * ds * FP32_BYTES, weight_stream=stream,
                collective="tp_allreduce", fm_width=D))
        if channel_kind(model, i) == "moe":
            E, K = model["num_experts"], model["num_experts_per_tok"]
            tokens = B * S
            nodes.append(Node(
                f"l{i}.moe", "moe", i, S, E, B,
                flops=(2.0 * tokens * D * E + 2.0 * tokens * K * D * F * mats)
                * tm,
                weight_bytes=(E * mats * D * F + D * E) * BF16_BYTES,
                act_bytes=act,
                inner_bytes=(min(E, tokens * K) * mats * D * F * BF16_BYTES
                             + tokens * K * (D + (mats - 1) * F) * BF16_BYTES),
                ep_topk=K, collective="ep_alltoall", fm_width=D))
        else:
            nodes.append(Node(
                f"l{i}.ffn", "ffn", i, S, F, B,
                flops=2.0 * B * S * D * F * mats * tm,
                weight_bytes=mats * D * F * BF16_BYTES, act_bytes=act,
                inner_bytes=(mats - 1) * B * S * F * BF16_BYTES,
                weight_stream=stream, collective="tp_allreduce",
                fm_width=D))
    nodes.append(Node("final_norm", "norm", -1, S, D, B,
                      flops=5.0 * B * S * D * tm, weight_bytes=D * BF16_BYTES,
                      act_bytes=2.0 * B * S * D * BF16_BYTES,
                      elementwise=True, fm_width=D))
    s_head = 1 if mode == "prefill" else S      # prefill: last position only
    tied = model["tie_word_embeddings"]
    nodes.append(Node(
        "lm_head", "head", -1, S, V, B, flops=2.0 * B * s_head * D * V * tm,
        weight_bytes=0.0 if tied else V * D * BF16_BYTES,
        act_bytes=B * s_head * D * BF16_BYTES,
        inner_bytes=B * s_head * V * BF16_BYTES
        + (V * D * BF16_BYTES if tied and stream else 0.0),
        weight_stream=stream, collective="vocab_head", fm_width=D))
    return nodes


def cut_edges(nodes: Sequence[Node]) -> Tuple[int, ...]:
    """Edges where a cut may fall: between layers, and after the embedding."""
    return tuple(e for e in range(len(nodes) - 1)
                 if nodes[e].layer != nodes[e + 1].layer
                 or nodes[e].kind == "embed")


# ----------------------------------------------------------------------
# the platform: which fold values the mesh can realise
# ----------------------------------------------------------------------

class Mesh:
    def __init__(self, platform: dict):
        self.sizes = [int(s) for _, s in platform["mesh_axes"]]
        self.chips = int(np.prod(self.sizes))
        vals = set()
        for r in range(len(self.sizes) + 1):
            for combo in itertools.combinations(self.sizes, r):
                vals.add(int(np.prod(combo)) if combo else 1)
        self.fold_values = sorted(vals)
        self._real: Dict[Tuple[int, int, int], bool] = {}
        self._table: Optional[np.ndarray] = None

    def realizable(self, si: int, so: int, kk: int) -> bool:
        """Each fold is the product of its own disjoint set of mesh axes."""
        key = (si, so, kk)
        hit = self._real.get(key)
        if hit is None:
            hit = False
            for owner in itertools.product(range(4), repeat=len(self.sizes)):
                prod = [1, 1, 1, 1]
                for axis, o in enumerate(owner):
                    prod[o] *= self.sizes[axis]
                if prod[:3] == [si, so, kk]:
                    hit = True
                    break
            self._real[key] = hit
        return hit

    def realizable_rows(self, si, so, kk) -> np.ndarray:
        """``realizable`` over int arrays of folds."""
        vals = self.fold_values
        if self._table is None:
            # table[a, b, c] for folds of value index a, b, c; index len(vals)
            # stands for any value the mesh has no fold of
            k = len(vals)
            self._table = np.zeros((k + 1,) * 3, bool)
            for a, b, c in itertools.product(range(k), repeat=3):
                self._table[a, b, c] = self.realizable(vals[a], vals[b],
                                                       vals[c])
            self._index = np.full(vals[-1] + 2, k)
            self._index[vals] = np.arange(k)
        top = len(self._index) - 1
        ix = [self._index[np.minimum(x, top)] for x in (si, so, kk)]
        return self._table[ix[0], ix[1], ix[2]]


# ----------------------------------------------------------------------
# a problem: graph + platform + objective; batch evaluation of designs
# ----------------------------------------------------------------------

@dataclass(frozen=True)
class Design:
    cuts: Tuple[int, ...]
    s_in: Tuple[int, ...]
    s_out: Tuple[int, ...]
    kern: Tuple[int, ...]

    def fold(self, var: str) -> Tuple[int, ...]:
        return getattr(self, var)

    def with_cuts(self, cuts) -> "Design":
        return Design(tuple(sorted(set(cuts))), self.s_in, self.s_out,
                      self.kern)


@dataclass
class Scores:
    """Per-design results of one batch evaluation (arrays over rows)."""
    objective: np.ndarray
    feasible: np.ndarray
    structural: np.ndarray        # violations a fold change cannot repair
    node_time: np.ndarray         # [N, n]
    resident: np.ndarray          # [N, n]
    collective: np.ndarray        # [N, n]
    part_time: np.ndarray         # [N, n]: partition p's time in column p
    part_resident: np.ndarray     # [N, n]
    pid: np.ndarray               # [N, n] partition of each node


class Problem:
    """One mapping problem of the reference."""

    def __init__(self, config: dict, shape: dict, objective: str,
                 dtype=np.float64):
        opts = config["model_options"]
        if (config["backend"], config["exec_model"]) != ("spmd", "spmd") \
                or opts["zero1"] or opts["seq_parallel_stash"]:
            raise NotImplementedError(
                "the reference covers the spmd backend and execution model "
                "with the default cost-model switches")
        self.objective = objective
        self.amortisation = int(config["batch_amortisation"])
        self.opts = opts
        self.plat = config["platform"]
        self.mesh = Mesh(self.plat)
        self.nodes = build_graph(config["model"], shape)
        self.mode = shape["mode"]
        self.n = len(self.nodes)
        self.cut_edges = cut_edges(self.nodes)
        self.dt = dtype
        self._cache: Dict[Design, Tuple[float, bool]] = {}
        self._scores: Dict[Design, Scores] = {}
        self.sg = [nd.scan_group for nd in self.nodes]
        self.elementwise = [j for j, nd in enumerate(self.nodes)
                            if nd.elementwise]
        f = lambda attr: np.array([float(getattr(nd, attr))
                                   for nd in self.nodes])
        self.col = {a: f(a) for a in (
            "rows", "col_div", "batch", "flops", "weight_bytes", "act_bytes",
            "inner_bytes", "state_bytes", "kv_bytes", "carry_bytes",
            "kv_limit", "ep_topk", "fm_width")}
        self.dims = {"s_in": np.array([nd.rows for nd in self.nodes]),
                     "s_out": np.array([nd.col_div for nd in self.nodes]),
                     "kern": np.array([nd.batch for nd in self.nodes])}
        self.menus = {var: [[v for v in self.mesh.fold_values
                             if self.dims[var][j] % v == 0]
                            for j in range(self.n)] for var in VARS}
        kinds = np.array([nd.kind for nd in self.nodes])
        colls = np.array([nd.collective for nd in self.nodes])
        self.kinds = {
            "attn": np.isin(kinds, ATTENTION_KINDS), "head": kinds == "head",
            "elementwise": np.array([nd.elementwise for nd in self.nodes]),
            "allreduce": np.isin(colls, ("tp_allreduce", "vocab_allreduce")),
            "alltoall": colls == "ep_alltoall",
            "vocab_head": colls == "vocab_head"}
        self.stream = np.array([nd.weight_stream for nd in self.nodes])
        self.cut_allowed = np.zeros(self.n - 1, bool)
        self.cut_allowed[list(self.cut_edges)] = True
        self.groups = [np.array([j for j, nd in enumerate(self.nodes)
                                 if nd.scan_group == g])
                       for g in sorted(set(SCAN_GROUP.values()))]
        self.groups = [m for m in self.groups if len(m)]

    # -- design moves -----------------------------------------------------
    def partition_of(self, i: int, cuts: Sequence[int]) -> range:
        lo, hi = 0, self.n
        for c in sorted(cuts):
            if c < i:
                lo = c + 1
            else:
                hi = min(hi, c + 1)
                break
        return range(lo, hi)

    def scope(self, i: int, cuts: Sequence[int]) -> List[int]:
        g = self.sg[i]
        if g < 0:
            return [i]
        return [j for j in self.partition_of(i, cuts) if self.sg[j] == g]

    def tie(self, d: Design) -> Design:
        """Scan groups take their first member's folds, per partition;
        elementwise nodes keep ``s_out == s_in``."""
        si, so, kk = list(d.s_in), list(d.s_out), list(d.kern)
        bounds = [0] + [c + 1 for c in d.cuts] + [self.n]
        sg = self.sg
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            first = {}
            for j in range(lo, hi):
                g = sg[j]
                if g < 0:
                    continue
                if g in first:
                    si[j], so[j], kk[j] = first[g]
                else:
                    first[g] = (si[j], so[j], kk[j])
        for j in self.elementwise:
            so[j] = si[j]
        return Design(d.cuts, tuple(si), tuple(so), tuple(kk))

    def set_fold(self, d: Design, i: int, var: str, value: int) -> Design:
        """Give node ``i``'s tied scope the fold ``value``, each node
        walking down to its nearest divisor, then re-tie."""
        folds = {v: list(d.fold(v)) for v in VARS}
        for j in self.scope(i, d.cuts):
            val = value
            while val > 1 and self.dims[var][j] % val:
                val -= 1
            folds[var][j] = val
        return self.tie(Design(d.cuts, tuple(folds["s_in"]),
                               tuple(folds["s_out"]), tuple(folds["kern"])))

    def initial(self) -> Design:
        ones = (1,) * self.n
        return self.tie(Design(self.cut_edges, ones, ones, ones))

    def partitions(self, cuts: Sequence[int]) -> List[List[int]]:
        bounds = [0] + [c + 1 for c in sorted(cuts)] + [self.n]
        return [list(range(lo, hi)) for lo, hi in zip(bounds[:-1], bounds[1:])]

    # -- evaluation ---------------------------------------------------------
    def evaluate(self, si, so, kk, cb) -> Scores:
        """Score N designs: fold arrays ``[N, n]``, cut mask ``[N, n-1]``.

        Each formula is written in the order of its terms as the cost
        model states it, so that float64 results repeat to the last bit.
        """
        dt, col, plat, opts = self.dt, self.col, self.plat, self.opts
        c = lambda x: np.asarray(x, dt)
        si, so, kk = (np.asarray(a, np.int64) for a in (si, so, kk))
        cb = np.asarray(cb, bool)
        N, n = si.shape
        k_ = self.kinds
        train = self.mode == "train"
        fsi, fso, fkk = c(si), c(so), c(kk)
        chips = fsi * fso * fkk
        b_in = fsi                                  # no node keeps rows inside
        w = c(col["weight_bytes"]) / fso

        compute = c(col["flops"]) / chips / c(plat["peak_flops"]
                                              * opts["mxu_efficiency"])
        kvl = c(col["kv_limit"])
        has_kvl = (col["kv_limit"] > 0)[None, :]
        kv_div = np.where(has_kvl, np.minimum(fso, kvl), fso)
        attn = k_["attn"][None, :]
        state_div = np.where(attn, fkk * np.maximum(kv_div, c(1)) * fsi,
                             fkk * fso)
        state_rep = np.where(attn & has_kvl & (so > col["kv_limit"]),
                             fso / kv_div, c(1))
        state = c(col["state_bytes"]) * state_rep / state_div
        traffic = c(col["act_bytes"]) / (b_in * fkk) \
            + c(col["inner_bytes"]) / chips
        if train:
            hbm = traffic * c(3) + c(2) * w
        else:
            hbm = traffic + np.where(self.stream[None, :], w, c(0)) + state
        memory = hbm / c(plat["hbm_bw"])
        coll = self._collective_bytes(si, so, kk, fsi, fso, fkk)
        collective = coll / c(plat["ici_bw"]) \
            * c(1.0 - opts["overlap_collectives"])

        fm = c(col["batch"] * col["rows"] * col["fm_width"] * BF16_BYTES)
        if train:
            # bf16 weight, fp32 gradient and Adam m, v: 7x the weight bytes;
            # plus one boundary featuremap stashed for rematerialisation
            resident = w * c(7) + fm / (fsi * fkk)
            head = c(3) * c(col["inner_bytes"]) / (
                b_in * fkk * np.maximum(fso, c(1)))
            resident = resident + np.where(k_["head"][None, :], head, c(0))
        else:
            resident = w + state + c(2) * fm / (b_in * fkk)

        node_time = np.maximum(np.maximum(compute, memory), collective)

        # partitions; every sum runs in node order
        pid = np.zeros((N, n), np.int64)
        pid[:, 1:] = np.cumsum(cb, axis=1)
        n_parts = pid[:, -1] + 1
        rows2 = np.broadcast_to(np.arange(N)[:, None], (N, n))
        seg = lambda vals: self._seg_sum(rows2, pid, vals)
        part_nodes = seg(node_time)
        change = ((si[:, :-1] != si[:, 1:]) | (kk[:, :-1] != kk[:, 1:])) \
            & ~cb
        reshard = self._seg_sum(rows2[:, :-1], pid[:, :-1], np.where(
            change, c(fm[:-1] / plat["ici_bw"]), c(0)))
        live = np.arange(n)[None, :] < n_parts[:, None]
        part_time = np.where(live, part_nodes + reshard, c(0))
        part_w = seg(w)
        later = live & (np.arange(n)[None, :] >= 1)
        reconf = np.cumsum(np.where(
            later, c(plat["reconf_fixed_s"]) + part_w / c(plat["dma_bw"]),
            c(0)), axis=1)[:, -1]
        total = np.cumsum(part_time, axis=1)[:, -1]
        latency = total + reconf
        amort = c(self.amortisation)
        throughput = amort / (amort * total + reconf)
        obj = latency if self.objective == "latency" else -throughput

        structural = self._structural(si, so, kk, cb, pid)
        bad = structural > 0
        multi = n_parts > 1
        start = np.ones((N, n), bool)
        start[:, 1:] = cb
        end = np.ones((N, n), bool)
        end[:, :-1] = cb
        boundary = seg(np.where(start, fm, c(0)) + np.where(end, fm, c(0)))
        part_res = seg(resident)
        over = part_res + np.where(multi[:, None],
                                   boundary / c(self.mesh.chips), c(0))
        bad |= (live & (over > c(plat["hbm_bytes"]))).any(axis=1)
        busy = part_nodes > 0
        stream_bw = boundary / np.where(busy, part_nodes, c(1))
        bad |= (live & busy & multi[:, None]
                & (stream_bw > c(plat["hbm_bw"] * self.mesh.chips))
                ).any(axis=1)
        return Scores(objective=np.asarray(obj, np.float64), feasible=~bad,
                      structural=structural, node_time=node_time,
                      resident=resident, collective=coll, part_time=part_time,
                      part_resident=part_res, pid=pid)

    def _seg_sum(self, rows, pid, vals):
        """Per-partition sums, node by node in order."""
        out = np.zeros((rows.shape[0], self.n), self.dt)
        if not pid.any():                   # one partition: a running sum
            out[:, 0] = np.cumsum(vals, axis=1)[:, -1]
        else:
            np.add.at(out, (rows, pid), vals)
        return out

    def _collective_bytes(self, si, so, kk, fsi, fso, fkk):
        """Per-chip collective bytes of every node (ring algorithms)."""
        c = lambda x: np.asarray(x, self.dt)
        col, k_ = self.col, self.kinds
        tmult = c(2 if self.mode == "train" else 1)
        shard = c(col["batch"] * col["rows"] * col["fm_width"] * BF16_BYTES) \
            / (fsi * fkk)
        allreduce = c(2) * (fso - c(1)) / fso * shard * tmult
        tokens = c(col["batch"] * col["rows"]) / (fsi * fkk)
        alltoall = c(2) * tokens * c(np.maximum(col["ep_topk"], 1)) \
            * c(col["fm_width"]) * c(BF16_BYTES) * (fso - c(1)) / fso * tmult
        softmax = c(2 * 8.0 * col["batch"] * col["rows"]) / (fsi * fkk)
        out = np.where(k_["allreduce"][None, :], allreduce, c(0))
        out = np.where(k_["alltoall"][None, :], alltoall, out)
        out = np.where(k_["vocab_head"][None, :], softmax, out)
        out = np.where(so > 1, out, c(0))
        kvl = c(col["kv_limit"])
        kv_div = np.where((col["kv_limit"] > 0)[None, :],
                          np.minimum(fso, kvl), np.maximum(fso, c(1))) * fkk
        ring_kv = c(col["kv_bytes"]) / kv_div * (fsi - c(1)) / fsi * tmult
        carry = c(col["carry_bytes"]) / fkk * (fsi - c(1)) / fsi * tmult
        seq = np.where((col["kv_bytes"] > 0)[None, :], ring_kv,
                       np.where((col["carry_bytes"] > 0)[None, :], carry,
                                c(0)))
        out = out + np.where(si > 1, seq, c(0))
        if self.mode == "train":
            grad = c(col["weight_bytes"]) / fso \
                * c(2.0 * self.opts["grad_compression"])
            dp = c(2) * (fkk - c(1)) / fkk * grad
            out = out + np.where((kk > 1) & (col["weight_bytes"] > 0)[None, :],
                                 dp, c(0))
        return out

    def _structural(self, si, so, kk, cb, pid) -> np.ndarray:
        """Count of violations no fold raise repairs: a cut off a layer
        boundary, a fold that does not divide its dimension or that the
        mesh cannot realise, an elementwise node with ``s_in != s_out``,
        and a scan-group member whose folds differ from the group's first
        member in the same partition."""
        count = (cb & ~self.cut_allowed[None, :]).sum(axis=1)
        for var, arr in (("s_in", si), ("s_out", so), ("kern", kk)):
            count += (self.dims[var][None, :] % arr != 0).sum(axis=1)
        count += (~self.mesh.realizable_rows(si, so, kk)).sum(axis=1)
        count += (self.kinds["elementwise"][None, :] & (si != so)).sum(axis=1)
        for members in self.groups:
            p = pid[:, members]
            first = np.ones(p.shape, bool)
            first[:, 1:] = p[:, 1:] != p[:, :-1]
            idx = np.maximum.accumulate(
                np.where(first, np.arange(len(members))[None, :], 0), axis=1)
            trip = np.stack([si[:, members], so[:, members], kk[:, members]])
            anchor = np.take_along_axis(trip, idx[None, :, :], axis=2)
            count += (~first & (trip != anchor).any(axis=0)).sum(axis=1)
        return count

    # -- designs one at a time --------------------------------------------
    def arrays(self, designs: Sequence[Design]):
        si = np.array([d.s_in for d in designs], np.int64)
        so = np.array([d.s_out for d in designs], np.int64)
        kk = np.array([d.kern for d in designs], np.int64)
        cb = np.zeros((len(designs), self.n - 1), bool)
        for r, d in enumerate(designs):
            cb[r, list(d.cuts)] = True
        return si, so, kk, cb

    def score(self, designs: Sequence[Design]) -> Scores:
        return self.evaluate(*self.arrays(designs))

    def one(self, d: Design) -> Scores:
        """Scores of one design, memoised."""
        hit = self._scores.get(d)
        if hit is None:
            hit = self._scores[d] = self.score([d])
        return hit

    def objective_of(self, d: Design) -> Tuple[float, bool]:
        """(objective, feasible) of one design, memoised."""
        hit = self._cache.get(d)
        if hit is None:
            s = self.one(d)
            hit = (float(s.objective[0]), bool(s.feasible[0]))
            self._cache[d] = hit
        return hit
