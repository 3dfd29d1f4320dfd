"""Plain reference of the mapping problem for a DeepSeek-sparse-attention
MoE chain (DeepSeek-V3.2).

The chain of ``latent_moe.py`` (float64, the spmd backend and execution
model, the cost model's default switches, prefill and decode), with each
latent-attention node replaced by a DeepSeek sparse attention (DSA) node:
latent attention over the positions a lightning indexer selects. From
``latent_moe.py`` and ``model.py`` it takes the design, the mesh, the cut
rule and the non-attention nodes (dense layers, shared and routed
experts, embedding, norm, head); it imports nothing of the program.

The DSA node, by the published description (DeepSeek-V3.2-Exp report).
Symbols: D hidden, H heads, qr / kvr the q / kv latent ranks, dn / dr / dv
the nope / rope / value head sizes, HI / dI the indexer's heads and head
size, K ``index_topk``; batch B, query rows S (1 in decode), context L.

- Indexer: I(t, s) = sum_j w(t, j) ReLU(qI(t, j) . kI(s)); qI from the
  query latent (qr -> HI*dI), kI from the normed input (D -> dI, one
  vector a token, with a LayerNorm of scale and bias), w from it
  (D -> HI). Weights qr*HI*dI + D*dI + 2*dI + D*HI. FLOPs
  2*B*S*(qr*HI*dI + D*dI + D*HI) + 2*B*HI*dI*P, P = S(S+1)/2 score pairs
  in prefill, S*L in decode. The ReLU, the head weighting and the top-k
  are not matrix products and are not counted.
- Selection: query t takes the top min(K, t+1) positions s <= t, ties to
  the lower position; the int32 positions are written by the indexer and
  read by the core (inner bytes, 2 * 4 bytes a position).
- Core: latent attention in the absorbed form in prefill and decode,
  over the selected positions. FLOPs 2*B*S*(D*qr + qr*H*(dn+dr)
  + D*(kvr+dr) + H*dn*kvr + H*kvr*dv + H*dv*D) + 2*B*H*Ksel*(2*kvr + dr),
  Ksel = sum over t < S of min(K, t+1) in prefill, min(K, L) in decode.
  Inner bytes, bf16 a token: the q and kv latents (qr + kvr + dr) and per
  head the query (dn + dr), its latent form (kvr + dr), the latent
  output (kvr) and the output (dv).
- Caches: latent B*L*(kvr+dr)*2 bytes, index keys B*L*(dI+4) bytes (FP8
  keys, a float32 scale a token). Both divide over the batch and
  sequence folds, never the head fold, and both stay resident.

Three rules no other node has:

a. the indexer is whole on every head shard: its FLOPs divide by
   ``s_in * kern`` only, its weights are whole on every chip (resident,
   streamed each step, and loaded at a reconfiguration);
b. a decode step reads the index keys and the selected latent rows,
   B*min(K,L)*(kvr+dr)*2 bytes, over (kern, s_in); a prefill writes
   both caches;
c. a split-KV decode (``s_in > 1``) merges the global top-K by an
   all-gather of (score, position) pairs, B/kern * min(K,L) * 8 *
   (s_in - 1) bytes a chip, before the latent-width partial combine; a
   prefill's ring exchange (``s_in > 1``) carries the index keys beside
   the latent cache.

The rule-based search is ``search.py``'s, with rule a in the
reconfiguration time its descent weighs (``_t_conf``).
"""
from __future__ import annotations

import types
from dataclasses import dataclass
from typing import List

import numpy as np

from . import latent_moe, search
from .latent_moe import BF16_BYTES

FP32_BYTES = 4.0
#: scan groups: nodes of one kind inside one partition share their folds
SCAN_GROUP = dict(latent_moe.SCAN_GROUP, dsa=latent_moe.SCAN_GROUP["mla"])


@dataclass(frozen=True)
class Node(latent_moe.Node):
    indexer_flops: float = 0.0        # FLOPs replicated over the head fold
    indexer_weight_bytes: float = 0.0  # weights whole on every head shard
    state_io_bytes: float = 0.0       # cache bytes a step writes or reads
    index_topk: int = 0               # positions a decode query selects

    @property
    def scan_group(self) -> int:
        return SCAN_GROUP.get(self.kind, -1)


def indexer_weights(model: dict) -> int:
    D, qr = model["hidden_size"], model["q_lora_rank"]
    HI, dI = model["index_n_heads"], model["index_head_dim"]
    return qr * HI * dI + D * dI + 2 * dI + D * HI


def selected(K: int, S: int, L: int, decode: bool) -> int:
    """Ksel: the positions a sequence's queries attend to, summed."""
    if decode:
        return min(K, L)
    t = min(K, S)
    return t * (t + 1) // 2 + (S - t) * K


def build_graph(model: dict, shape: dict) -> List[latent_moe.Node]:
    """``latent_moe.build_graph``'s chain with a DSA node in place of each
    latent-attention node."""
    decode = shape["mode"] == "decode"
    B, L = shape["global_batch"], shape["seq_len"]
    S = 1 if decode else L
    D, H = model["hidden_size"], model["num_attention_heads"]
    qr, kvr = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    HI, dI = model["index_n_heads"], model["index_head_dim"]
    K = model["index_topk"]
    pairs = S * L if decode else S * (S + 1) // 2
    indexer = 2.0 * B * S * (qr * HI * dI + D * dI + D * HI) \
        + 2.0 * B * HI * dI * pairs
    ksel = selected(K, S, L, decode)
    core = 2.0 * B * S * (D * qr + qr * H * (dn + dr) + D * (kvr + dr)
                          + H * dn * kvr + H * kvr * dv + H * dv * D) \
        + 2.0 * B * H * ksel * (2 * kvr + dr)
    w_idx = indexer_weights(model) * BF16_BYTES
    keys = B * L * (dI + FP32_BYTES)
    caches = B * L * (kvr + dr) * BF16_BYTES + keys
    io = keys + B * min(K, L) * (kvr + dr) * BF16_BYTES if decode else caches
    out = []
    for nd in latent_moe.build_graph(model, shape):
        if nd.kind != "mla":
            out.append(nd)
            continue
        fields = {f: getattr(nd, f)
                  for f in latent_moe.Node.__dataclass_fields__}
        fields.update(
            name=f"l{nd.layer}.dsa", kind="dsa", flops=core + indexer,
            weight_bytes=nd.weight_bytes + w_idx,
            inner_bytes=B * S * (qr + kvr + dr + H * (dn + dr + kvr + dr
                                                      + kvr + dv))
            * BF16_BYTES + 2.0 * B * ksel * 4.0,
            state_bytes=caches, kv_bytes=caches)
        out.append(Node(**fields, indexer_flops=indexer,
                        indexer_weight_bytes=w_idx, state_io_bytes=io,
                        index_topk=min(K, L)))
    return out


def chip_weights(nd, s_out: int) -> float:
    """Weight bytes a chip holds of a node: the head fold divides them,
    except a DSA node's indexer, whole on every head shard (rule a)."""
    w_idx = getattr(nd, "indexer_weight_bytes", 0.0)
    if nd.kind == "dsa":
        return (nd.weight_bytes - w_idx) / s_out + w_idx
    return nd.weight_bytes / s_out


class Problem(latent_moe.Problem):
    """``latent_moe.Problem`` on the DSA chain. The chain's structure
    (rows, channels, batch, kinds' scan groups, cut edges) is the latent
    chain's, so only the node columns and the terms of the three rules
    change."""

    def __init__(self, config: dict, shape: dict, objective: str,
                 dtype=np.float64):
        super().__init__(config, shape, objective, dtype)
        self.nodes = build_graph(config["model"], shape)
        f = lambda attr: np.array([float(getattr(nd, attr, 0.0))
                                   for nd in self.nodes])
        self.col = {a: f(a) for a in (
            "rows", "col_div", "batch", "flops", "weight_bytes", "act_bytes",
            "inner_bytes", "state_bytes", "kv_bytes", "latent", "ep_topk",
            "fm_width", "indexer_flops", "indexer_weight_bytes",
            "state_io_bytes", "index_topk")}
        kinds = np.array([nd.kind for nd in self.nodes])
        self.kinds["sparse"] = kinds == "dsa"
        self.kinds["latent"] = self.kinds["sparse"]

    def evaluate(self, si, so, kk, cb) -> latent_moe.Scores:
        """``latent_moe.Problem.evaluate`` with rules a and b (rule c is
        in ``_collective_bytes``), each formula in the order of its terms
        as the cost model states it."""
        dt, col, plat, opts = self.dt, self.col, self.plat, self.opts
        c = lambda x: np.asarray(x, dt)
        si, so, kk = (np.asarray(a, np.int64) for a in (si, so, kk))
        cb = np.asarray(cb, bool)
        N, n = si.shape
        k_ = self.kinds
        decode = self.mode == "decode"
        fsi, fso, fkk = c(si), c(so), c(kk)
        chips = fsi * fso * fkk
        b_in = np.where(self.internal[None, :], c(1), fsi)
        # rule a: the indexer's weights are whole on every head shard,
        # its FLOPs divide over the sequence and batch folds only
        w_idx = c(col["indexer_weight_bytes"])
        w = (c(col["weight_bytes"]) - w_idx) / fso + w_idx
        f_idx = c(col["indexer_flops"])
        compute = ((c(col["flops"]) - f_idx) / chips + f_idx / (fsi * fkk)) \
            / c(plat["peak_flops"] * opts["mxu_efficiency"])
        state_div = np.where(k_["latent"][None, :], fkk * fsi, fkk * fso)
        state = c(col["state_bytes"]) / state_div
        # rule b: the bytes a step moves, not the bytes it holds
        io = np.where(k_["sparse"][None, :],
                      c(col["state_io_bytes"]) / state_div, state)
        traffic = c(col["act_bytes"]) / (b_in * fkk) \
            + c(col["inner_bytes"]) / chips
        hbm = traffic + np.where(self.stream[None, :], w, c(0)) + io
        memory = hbm / c(plat["hbm_bw"])
        coll = self._collective_bytes(si, so, kk, fsi, fso, fkk, b_in)
        collective = coll / c(plat["ici_bw"]) \
            * c(1.0 - opts["overlap_collectives"])

        fm = c(col["batch"] * col["rows"] * col["fm_width"] * BF16_BYTES)
        step_rows = np.where(decode, 1.0, col["rows"])
        step_fm = c(col["batch"] * step_rows * col["fm_width"] * BF16_BYTES)
        resident = w + state + c(2) * step_fm / (b_in * fkk)

        node_time = np.maximum(np.maximum(compute, memory), collective)

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
        return latent_moe.Scores(
            objective=np.asarray(obj, np.float64), feasible=~bad,
            structural=structural, node_time=node_time, resident=resident,
            collective=coll, part_time=part_time, part_resident=part_res,
            pid=pid)

    def _collective_bytes(self, si, so, kk, fsi, fso, fkk, b_in):
        """``latent_moe``'s collectives (the latent combine and ring read
        the DSA node's width and both caches), then rule c's top-K merge
        of a split-KV decode."""
        out = super()._collective_bytes(si, so, kk, fsi, fso, fkk, b_in)
        if self.mode != "decode":
            return out
        c = lambda x: np.asarray(x, self.dt)
        merge = c(self.col["batch"]) / fkk * c(self.col["index_topk"]) \
            * c(8) * (fsi - c(1))
        return out + np.where(self.kinds["sparse"][None, :] & (si > 1),
                              merge, c(0))


def _t_conf(P, part, d) -> float:
    """A partition's reconfiguration time: the fixed swap cost and every
    chip loading its weight shard, each indexer whole (rule a)."""
    w = 0.0
    for i in part:
        w += chip_weights(P.nodes[i], d.s_out[i])
    return P.plat["reconf_fixed_s"] + w / P.plat["dma_bw"]


def _with_names(fn, names: dict):
    """``fn`` with its module names looked up in ``names``."""
    return types.FunctionType(fn.__code__, names, fn.__name__,
                              fn.__defaults__)


#: ``search.py``'s searches; the rule-based descent weighs ``_t_conf``
_NAMES = dict(vars(search), _t_conf=_t_conf)
_NAMES["descend"] = _with_names(search.descend, _NAMES)
SEARCHES = {"rule_based": _with_names(search.rule_based, _NAMES),
            "brute_force": search.brute_force}
