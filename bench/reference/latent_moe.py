"""Plain reference of the mapping problem for a latent-attention MoE chain.

The same semantics as ``model.py`` (float64, the spmd backend and
execution model, the cost model's default switches), for a model that
``model.py``'s chain does not know: multi-head latent attention (MLA) in
every layer, a dense channel mixer in the first ``first_k_dense_replace``
layers, and after them a shared expert beside the routed experts
(Kimi-K2, DeepSeek-V3). It covers the ``prefill`` and ``decode`` modes.

What is new against ``model.py``, as the mapping optimiser states it:

- ``mla`` nodes. Prefill runs the non-absorbed form (the latent expanded
  to per-head keys and values); decode runs the absorbed form against the
  latent cache, one token a sequence. The cache is one
  ``kv_lora_rank + qk_rope_head_dim`` vector per token, shared by all
  heads, so it divides over the batch and sequence folds only, never over
  the head fold: its per-chip bytes do not depend on ``s_out``, its ring
  exchange (prefill, ``s_in > 1``) is the whole cache of a batch shard, and
  the decode split-KV combine sends per-head partials ``kv_lora_rank``
  wide.
- ``shared_expert`` nodes: a dense channel mixer of width
  ``n_shared_experts * moe_intermediate_size``, with its own scan group.
- decode: every node but ``mla`` sees one row a sequence; the ``mla``
  node's rows are the cache positions and stay inside it (its boundary
  layout has no row fold); the head all-gathers its sharded logits.

It reads only the configuration and traffic files and imports nothing of
the program under test. From ``model.py`` it takes the design, the mesh
and the cut rule, which know nothing of node kinds.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from .model import VARS, Design, Mesh, cut_edges

BF16_BYTES = 2.0
#: scan groups: nodes of one kind inside one partition share their folds
SCAN_GROUP = {"mla": 0, "ffn": 1, "shared_expert": 2, "moe": 3}


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
    latent: int = 0               # latent cache width (kv_lora_rank)
    internal_rows: bool = False   # decode: rows are the cache, kept inside
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

def mla_weights(model: dict) -> int:
    D, H = model["hidden_size"], model["num_attention_heads"]
    qr, kvr = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    return (D * qr + qr + qr * H * (dn + dr) + D * (kvr + dr) + kvr
            + kvr * H * (dn + dv) + H * dv * D)


def is_moe_layer(model: dict, i: int) -> bool:
    return (model.get("n_routed_experts") or 0) > 0 \
        and i >= model["first_k_dense_replace"] \
        and i % model.get("moe_layer_freq", 1) == 0


def build_graph(model: dict, shape: dict) -> List[Node]:
    """The node chain of ``model`` (published sizes, HF key names) at
    ``shape`` (``seq_len``, ``global_batch``, ``mode``)."""
    mode = shape["mode"]
    if mode not in ("prefill", "decode"):
        raise NotImplementedError(f"the latent reference covers prefill "
                                  f"and decode, not {mode!r}")
    decode = mode == "decode"
    B, L = shape["global_batch"], shape["seq_len"]
    S = 1 if decode else L                       # query rows this step
    D, V = model["hidden_size"], model["vocab_size"]
    H = model["num_attention_heads"]
    qr, kvr = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    mats = 3 if model["hidden_act"] == "silu" else 2
    act = 4.0 * B * S * D * BF16_BYTES
    W = mla_weights(model)

    def dense(name, kind, i, F):
        return Node(name, kind, i, S, F, B,
                    flops=2.0 * B * S * D * F * mats,
                    weight_bytes=mats * D * F * BF16_BYTES, act_bytes=act,
                    inner_bytes=(mats - 1) * B * S * F * BF16_BYTES,
                    weight_stream=True, collective="tp_allreduce",
                    fm_width=D)

    nodes = [Node("embed", "embed", -1, S, V, B, flops=B * S * D,
                  weight_bytes=V * D * BF16_BYTES,
                  act_bytes=B * S * D * BF16_BYTES + B * S * 4.0,
                  collective="vocab_allreduce", fm_width=D)]
    for i in range(model["num_hidden_layers"]):
        if decode:
            # absorbed: q_nope folds into the latent, v leaves through it
            proj = 2.0 * B * (D * qr + qr * H * (dn + dr) + D * (kvr + dr)
                              + H * dn * kvr + H * kvr * dv + H * dv * D)
            sdpa = 2.0 * B * H * L * (2 * kvr + dr)
        else:
            proj = 2.0 * B * S * (W - qr - kvr)
            sdpa = 2.0 * B * H * S * L * (dn + dr + dv) * 0.5    # causal
        cache = B * L * (kvr + dr) * BF16_BYTES
        nodes.append(Node(
            f"l{i}.mla", "mla", i, L if decode else S, H, B,
            flops=proj + sdpa, weight_bytes=W * BF16_BYTES, act_bytes=act,
            inner_bytes=B * S * (qr + kvr + H * (2 * (dn + dr) + 2 * dv))
            * BF16_BYTES,
            state_bytes=cache, kv_bytes=cache, latent=kvr,
            internal_rows=decode, weight_stream=True,
            collective="tp_allreduce", fm_width=D))
        if is_moe_layer(model, i):
            F = model["moe_intermediate_size"]
            if model.get("n_shared_experts"):
                nodes.append(dense(f"l{i}.shared", "shared_expert", i,
                                   model["n_shared_experts"] * F))
            E, K = model["n_routed_experts"], model["num_experts_per_tok"]
            tokens = B * S
            nodes.append(Node(
                f"l{i}.moe", "moe", i, S, E, B,
                flops=2.0 * tokens * D * E + 2.0 * tokens * K * D * F * mats,
                weight_bytes=(E * mats * D * F + D * E) * BF16_BYTES,
                act_bytes=act,
                inner_bytes=(min(E, tokens * K) * mats * D * F * BF16_BYTES
                             + tokens * K * (D + (mats - 1) * F) * BF16_BYTES),
                ep_topk=K, collective="ep_alltoall", fm_width=D))
        else:
            nodes.append(dense(f"l{i}.ffn", "ffn", i,
                               model["intermediate_size"]))
    nodes.append(Node("final_norm", "norm", -1, S, D, B,
                      flops=5.0 * B * S * D, weight_bytes=D * BF16_BYTES,
                      act_bytes=2.0 * B * S * D * BF16_BYTES,
                      elementwise=True, fm_width=D))
    s_head = 1                   # prefill: last position only; decode: 1
    tied = model["tie_word_embeddings"]
    nodes.append(Node(
        "lm_head", "head", -1, S, V, B, flops=2.0 * B * s_head * D * V,
        weight_bytes=0.0 if tied else V * D * BF16_BYTES,
        act_bytes=B * s_head * D * BF16_BYTES,
        inner_bytes=B * s_head * V * BF16_BYTES
        + (V * D * BF16_BYTES if tied else 0.0),
        weight_stream=True, collective="vocab_head", fm_width=D))
    return nodes


# ----------------------------------------------------------------------
# a problem: graph + platform + objective; batch evaluation of designs
# ----------------------------------------------------------------------

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
    """One mapping problem of the latent reference (``model.Problem``'s
    interface, which the searches of ``search.py`` use)."""

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
            "inner_bytes", "state_bytes", "kv_bytes", "latent", "ep_topk",
            "fm_width")}
        self.internal = np.array([nd.internal_rows for nd in self.nodes])
        self.dims = {"s_in": np.array([nd.rows for nd in self.nodes]),
                     "s_out": np.array([nd.col_div for nd in self.nodes]),
                     "kern": np.array([nd.batch for nd in self.nodes])}
        self.menus = {var: [[v for v in self.mesh.fold_values
                             if self.dims[var][j] % v == 0]
                            for j in range(self.n)] for var in VARS}
        kinds = np.array([nd.kind for nd in self.nodes])
        colls = np.array([nd.collective for nd in self.nodes])
        self.kinds = {
            "latent": kinds == "mla",
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
        decode = self.mode == "decode"
        fsi, fso, fkk = c(si), c(so), c(kk)
        chips = fsi * fso * fkk
        # the boundary layout's row fold: none where the rows stay inside
        b_in = np.where(self.internal[None, :], c(1), fsi)
        w = c(col["weight_bytes"]) / fso

        compute = c(col["flops"]) / chips / c(plat["peak_flops"]
                                              * opts["mxu_efficiency"])
        # the latent cache divides over batch and sequence, not heads;
        # every other node's state (none here) over batch and channels
        state_div = np.where(k_["latent"][None, :], fkk * fsi, fkk * fso)
        state = c(col["state_bytes"]) / state_div
        traffic = c(col["act_bytes"]) / (b_in * fkk) \
            + c(col["inner_bytes"]) / chips
        hbm = traffic + np.where(self.stream[None, :], w, c(0)) + state
        memory = hbm / c(plat["hbm_bw"])
        coll = self._collective_bytes(si, so, kk, fsi, fso, fkk, b_in)
        collective = coll / c(plat["ici_bw"]) \
            * c(1.0 - opts["overlap_collectives"])

        # boundary featuremaps: a partition stages its whole rows in and
        # out; a step holds one row a sequence in decode
        fm = c(col["batch"] * col["rows"] * col["fm_width"] * BF16_BYTES)
        step_rows = np.where(decode, 1.0, col["rows"])
        step_fm = c(col["batch"] * step_rows * col["fm_width"] * BF16_BYTES)
        resident = w + state + c(2) * step_fm / (b_in * fkk)

        node_time = np.maximum(np.maximum(compute, memory), collective)

        # partitions; every sum runs in node order
        pid = np.zeros((N, n), np.int64)
        pid[:, 1:] = np.cumsum(cb, axis=1)
        n_parts = pid[:, -1] + 1
        rows2 = np.broadcast_to(np.arange(N)[:, None], (N, n))
        seg = lambda vals: self._seg_sum(rows2, pid, vals)
        part_nodes = seg(node_time)
        change = ((b_in[:, :-1] != b_in[:, 1:]) | (kk[:, :-1] != kk[:, 1:])) \
            & ~cb
        reshard = self._seg_sum(rows2[:, :-1], pid[:, :-1], np.where(
            change, c(step_fm[:-1] / plat["ici_bw"]), c(0)))
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

    def _collective_bytes(self, si, so, kk, fsi, fso, fkk, b_in):
        """Per-chip collective bytes of every node (ring algorithms)."""
        c = lambda x: np.asarray(x, self.dt)
        col, k_ = self.col, self.kinds
        rows = np.where(self.mode == "decode", 1.0, col["rows"])
        shard = c(col["batch"] * rows * col["fm_width"] * BF16_BYTES) \
            / (b_in * fkk)
        allreduce = c(2) * (fso - c(1)) / fso * shard
        tokens = c(col["batch"] * rows) / (b_in * fkk)
        alltoall = c(2) * tokens * c(np.maximum(col["ep_topk"], 1)) \
            * c(col["fm_width"]) * c(BF16_BYTES) * (fso - c(1)) / fso
        if self.mode == "decode":
            # all-gather of the sharded logits, for sampling
            head = c(col["col_div"] * BF16_BYTES) * c(col["batch"]) / fkk \
                * (fso - c(1)) / fso
        else:
            # distributed softmax: two statistics a token
            head = c(2 * 8.0 * col["batch"] * rows) / (b_in * fkk)
        out = np.where(k_["allreduce"][None, :], allreduce, c(0))
        out = np.where(k_["alltoall"][None, :], alltoall, out)
        out = np.where(k_["vocab_head"][None, :], head, out)
        out = np.where(so > 1, out, c(0))
        # s_in > 1 on the latent node: in decode the split-KV combine of
        # per-head partials, latent-wide, with their max and sum; in
        # prefill the ring exchange of the whole latent cache of a batch
        # shard (a latent node's channels are its heads)
        combine = c(col["batch"]) / fkk * c(col["col_div"]) / fso \
            * c(col["latent"] + 2.0) * c(4) * (fsi - c(1)) / fsi
        ring = c(col["kv_bytes"]) / fkk * (fsi - c(1)) / fsi
        seq = np.where(self.internal[None, :], combine, ring)
        out = out + np.where(k_["latent"][None, :] & (si > 1), seq, c(0))
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
