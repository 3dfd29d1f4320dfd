"""Parser: (ArchConfig, ShapeSpec) -> HD-Graph (paper §IV-A).

The backends' "customised IR" is our ArchConfig + execution mode; this module
translates every layer into HD-Graph computation nodes carrying the base
workload quantities (FLOPs, weight/activation/state bytes) from which the
performance and resource models derive t(n|s_I,s_O,k) and r(n|s_I,s_O,k).

Byte quantities assume bf16 (2B) activations/weights; fp32 (4B) SSM states.
Traffic conventions consumed by core/perfmodel.py:
  act_bytes    boundary featuremap traffic  -> folds by (k, boundary s_I)
  inner_bytes  node-internal traffic        -> folds by (k, s_I, s_O)
  state_bytes  KV / recurrent state         -> kind-specific folding
  weight_stream=True adds the node's weight shard to HBM traffic (inference
  reads weights every invocation; training accounting is handled separately).
"""
from __future__ import annotations

from typing import List

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.hdgraph import HDGraph, Node

BF16 = 2.0
FP32 = 4.0

# scan-group ids per node kind (nodes of the same kind within one partition
# tie their folding variables: they live in one stacked lax.scan).
_SCAN_GROUP = {
    "attn": 0,
    "ssm": 1,
    "ffn": 2,
    "moe": 3,
    "rwkv_tmix": 4,
    "rwkv_cmix": 5,
    "cross_attn": 6,
    "enc_attn": 7,
    "enc_ffn": 8,
    "mla": 9,
    "shared_expert": 10,
    "dsa": 11,
}


def _n_ffn_mats(arch: ArchConfig) -> int:
    return 3 if arch.act == "swiglu" else 2


def build_hdgraph(arch: ArchConfig, shape: ShapeSpec) -> HDGraph:
    mode = shape.mode
    B = shape.global_batch
    S = shape.seq_len if mode != "decode" else 1      # query rows this step
    L = shape.seq_len                                  # context length
    tm = 3.0 if mode == "train" else 1.0               # fwd+bwd FLOP multiplier
    stream = mode != "train"                           # weights re-read per step

    nodes: List[Node] = []

    # ------------------------- encoder (whisper) ----------------------
    if arch.encoder_layers and mode != "decode":
        Se = arch.num_frames or 1500
        for i in range(arch.encoder_layers):
            nodes.append(_attn_node(arch, f"enc{i}.attn", i, B, Se, Se, tm,
                                    mode="prefill", kind="enc_attn"))
            nodes.append(_ffn_node(arch, f"enc{i}.ffn", i, B, Se, tm, stream,
                                   kind="enc_ffn"))

    # --------------------------- embedding ----------------------------
    nodes.append(Node(
        name="embed", kind="embed", layer=-1,
        rows=S, cols=arch.vocab_size, batch=B,
        flops=B * S * arch.d_model,        # gather/copy cost, negligible compute
        weight_bytes=arch.vocab_size * arch.d_model * BF16,
        act_bytes=B * S * arch.d_model * BF16 + B * S * 4.0,
        col_divisor=arch.vocab_size,
        collective_kind="vocab_allreduce",
        train_multiplier=1.0,
        fm_width=arch.d_model,
    ))

    # ------------------------- decoder layers -------------------------
    for i in range(arch.num_layers):
        mixer = arch.layer_kind(i)
        if mixer == "attn":
            nodes.append(_attn_node(arch, f"l{i}.attn", i, B, S, L, tm, mode=mode))
            if arch.cross_attention:
                Se = arch.num_frames or 1500
                nodes.append(_attn_node(arch, f"l{i}.xattn", i, B, S, Se, tm,
                                        mode=mode, kind="cross_attn", causal=False))
        elif mixer == "mla":
            nodes.append(_mla_node(arch, f"l{i}.mla", i, B, S, L, tm, mode))
        elif mixer == "dsa":
            nodes.append(_dsa_node(arch, f"l{i}.dsa", i, B, S, L, tm, mode))
        elif mixer == "ssm":
            nodes.append(_ssm_node(arch, f"l{i}.ssm", i, B, S, tm, mode))
        elif mixer == "rwkv":
            nodes.append(_rwkv_tmix_node(arch, f"l{i}.tmix", i, B, S, tm, mode))
        # channel mixer
        fk = arch.ffn_kind(i)
        if mixer == "rwkv":
            nodes.append(_rwkv_cmix_node(arch, f"l{i}.cmix", i, B, S, tm, stream))
        elif fk == "moe":
            if arch.n_shared_experts:
                nodes.append(_ffn_node(
                    arch, f"l{i}.shared", i, B, S, tm, stream,
                    kind="shared_expert",
                    width=arch.n_shared_experts * arch.expert_d_ff))
            nodes.append(_moe_node(arch, f"l{i}.moe", i, B, S, tm))
        else:
            nodes.append(_ffn_node(arch, f"l{i}.ffn", i, B, S, tm, stream))

    # -------------------------- final norm + head ---------------------
    D, V = arch.d_model, arch.vocab_size
    nodes.append(Node(
        name="final_norm", kind="norm", layer=-1,
        rows=S, cols=D, batch=B,
        flops=5.0 * B * S * D * tm,
        weight_bytes=D * BF16,
        act_bytes=2.0 * B * S * D * BF16,
        elementwise=True,
        fm_width=D,
        train_multiplier=tm,
    ))
    # Prefill only needs the LAST position's logits (the serve step slices
    # before the head matmul) — decode computes its single new token.
    S_head = 1 if mode == "prefill" else S
    nodes.append(Node(
        name="lm_head", kind="head", layer=-1,
        rows=S, cols=V, batch=B,
        flops=2.0 * B * S_head * D * V * tm,
        weight_bytes=0.0 if arch.tie_embeddings else V * D * BF16,
        act_bytes=B * S_head * D * BF16,
        inner_bytes=B * S_head * V * BF16     # logits in vocab-sharded space
                    + (V * D * BF16 if arch.tie_embeddings and stream else 0.0),
        col_divisor=V,
        collective_kind="vocab_head",
        train_multiplier=tm,
        weight_stream=stream,
        fm_width=D,
    ))

    return HDGraph(nodes=nodes, arch_name=arch.name, shape_name=shape.name, mode=mode)


# ----------------------------------------------------------------------
# per-kind node constructors
# ----------------------------------------------------------------------

def _attn_node(arch: ArchConfig, name: str, layer: int, B: int, S: int, L: int,
               tm: float, mode: str, kind: str = "attn",
               causal: bool = True) -> Node:
    D, H, Hkv, dh = arch.d_model, arch.num_heads, arch.num_kv_heads, arch.head_dim
    qkv_flops = 2.0 * B * S * D * (H * dh + 2 * Hkv * dh)
    out_flops = 2.0 * B * S * (H * dh) * D
    causal_f = 0.5 if (causal and mode in ("train", "prefill") and S == L) else 1.0
    sdpa_flops = 2.0 * B * H * S * L * dh * 2.0 * causal_f
    wb = (D * H * dh + 2 * D * Hkv * dh + H * dh * D) * BF16
    kv_state = B * L * 2 * Hkv * dh * BF16
    decode = mode == "decode"
    return Node(
        name=name, kind=kind, layer=layer,
        rows=L if decode else S,              # decode: split-KV folding dim
        cols=H, batch=B,
        flops=(qkv_flops + out_flops + sdpa_flops) * tm,
        weight_bytes=wb,
        act_bytes=4.0 * B * S * D * BF16,
        inner_bytes=2.0 * B * S * H * dh * BF16,
        state_bytes=kv_state if mode != "train" else 0.0,
        kv_bytes=kv_state,
        col_divisor=H,
        kv_limit=Hkv,
        scan_group=_SCAN_GROUP[kind],
        collective_kind="tp_allreduce",
        train_multiplier=tm,
        weight_stream=(mode != "train"),
        internal_rows=decode,
        fm_width=D,
    )


def _mla_node(arch: ArchConfig, name: str, layer: int, B: int, S: int,
              L: int, tm: float, mode: str) -> Node:
    """Multi-head latent attention. Train/prefill run the non-absorbed
    form (the latent expanded to per-head k_nope and v); decode runs the
    absorbed form against the latent cache (S = 1). The cache is one
    ``kv_lora_rank + qk_rope_head_dim`` vector per token for all heads."""
    D, H = arch.d_model, arch.num_heads
    qr, kvr = arch.q_lora_rank, arch.kv_lora_rank
    dn, dr, dv = arch.qk_nope_head_dim, arch.qk_rope_head_dim, arch.v_head_dim
    W = arch.mla_weights()
    decode = mode == "decode"
    if decode:
        proj = 2.0 * B * (D * qr + qr * H * (dn + dr) + D * (kvr + dr)
                          + H * dn * kvr + H * kvr * dv + H * dv * D)
        sdpa = 2.0 * B * H * L * (2 * kvr + dr)
    else:
        causal_f = 0.5 if S == L else 1.0
        proj = 2.0 * B * S * (W - qr - kvr)
        sdpa = 2.0 * B * H * S * L * (dn + dr + dv) * causal_f
    kv_state = B * L * (kvr + dr) * BF16
    return Node(
        name=name, kind="mla", layer=layer,
        rows=L if decode else S,              # decode: split-KV folding dim
        cols=H, batch=B,
        flops=(proj + sdpa) * tm,
        weight_bytes=W * BF16,
        act_bytes=4.0 * B * S * D * BF16,
        # the q and kv latents, and the per-head q, k, v and output
        inner_bytes=B * S * (qr + kvr + H * (2 * (dn + dr) + 2 * dv)) * BF16,
        state_bytes=kv_state if mode != "train" else 0.0,
        kv_bytes=kv_state,
        col_divisor=H,
        latent_dim=kvr,
        scan_group=_SCAN_GROUP["mla"],
        collective_kind="tp_allreduce",
        train_multiplier=tm,
        weight_stream=(mode != "train"),
        internal_rows=decode,
        fm_width=D,
    )


def sparse_selected(topk: int, S: int, L: int, decode: bool) -> int:
    """Positions the sparse core attends to, summed over one sequence's
    queries: min(K, L) for the one decode query; sum over t < S of
    min(K, t + 1) in prefill, where query t sees positions s <= t."""
    if decode:
        return min(topk, L)
    t = min(topk, S)
    return t * (t + 1) // 2 + (S - t) * topk


def _dsa_node(arch: ArchConfig, name: str, layer: int, B: int, S: int,
              L: int, tm: float, mode: str) -> Node:
    """DeepSeek sparse attention (DeepSeek-V3.2): latent attention over
    the positions a lightning indexer selects.

    Symbols: D d_model, H heads, qr / kvr the q / kv latent ranks, dn / dr
    / dv the nope / rope / value head sizes, HI / dI the indexer's heads
    and head size, K ``index_topk``; batch B, query rows S, context L.

    Indexer: I(t, s) = sum_j w(t, j) * ReLU(qI(t, j) . kI(s)), with qI
    from the query latent c_q (qr -> HI*dI), kI from h (D -> dI, one
    LayerNorm'd vector a token for all indexer heads) and w from h
    (D -> HI). Weights qr*HI*dI + D*dI + 2*dI + D*HI. FLOPs
    2*B*S*(qr*HI*dI + D*dI + D*HI) + 2*B*HI*dI*P, with P = S(S+1)/2
    score pairs in prefill and S*L in decode. The ReLU, the head
    weighting and the top-k are elementwise or sorting work beside these
    matrix products and are not counted.

    Selection: for query t the top min(K, t+1) positions s <= t by
    I(t, s), ties to the lower position. The int32 positions are written
    by the indexer and read by the core: inner bytes.

    Core: latent attention in the absorbed MQA form, prefill and decode
    alike, over the selected positions only; each (kvr + dr)-wide latent
    row serves all H heads. FLOPs 2*B*S*(D*qr + qr*H*(dn+dr) + D*(kvr+dr)
    + H*dn*kvr + H*kvr*dv + H*dv*D) + 2*B*H*Ksel*(2*kvr + dr), Ksel
    counted per sequence (``sparse_selected``).

    Caches: the latent cache B*L*(kvr+dr)*2 bytes and the index-key cache
    B*L*(dI+4) bytes (FP8 keys and one float32 scale a token). Both shard
    over (k, s_I), never over the head fold (``latent_dim``).

    The rules the cost model adds for this kind (``perfmodel.node_eval``,
    ``array_program.eval_core``): a. the indexer is replicated over the
    head fold, its FLOPs divide by s_I*k and its weights are whole on
    every head shard (``indexer_flops``, ``indexer_weight_bytes``); b. a
    decode step reads the index keys and the selected latent rows
    B*min(K,L)*(kvr+dr)*2, both over (k, s_I), while both whole caches
    stay resident; a prefill writes both (``state_io_bytes``); c. a
    split-KV decode (s_I > 1) merges the top-K candidates with an
    all-gather of (score, position) pairs, B/k * min(K,L) * 8 * (s_I-1)
    bytes a chip, before the latent-width partial combine; a prefill
    ring exchange (s_I > 1) carries the index keys beside the latent
    cache (``kv_bytes``)."""
    D, H = arch.d_model, arch.num_heads
    qr, kvr = arch.q_lora_rank, arch.kv_lora_rank
    dn, dr, dv = arch.qk_nope_head_dim, arch.qk_rope_head_dim, arch.v_head_dim
    HI, dI, K = arch.index_n_heads, arch.index_head_dim, arch.index_topk
    decode = mode == "decode"
    pairs = S * L if decode else S * (S + 1) // 2
    indexer = 2.0 * B * S * (qr * HI * dI + D * dI + D * HI) \
        + 2.0 * B * HI * dI * pairs
    ksel = sparse_selected(K, S, L, decode)
    core = 2.0 * B * S * (D * qr + qr * H * (dn + dr) + D * (kvr + dr)
                          + H * dn * kvr + H * kvr * dv + H * dv * D) \
        + 2.0 * B * H * ksel * (2 * kvr + dr)
    w_idx = arch.indexer_weights() * BF16
    latent = B * L * (kvr + dr) * BF16
    keys = B * L * (dI + FP32)
    caches = latent + keys
    if decode:
        io = keys + B * min(K, L) * (kvr + dr) * BF16
    else:
        io = caches if mode != "train" else 0.0
    return Node(
        name=name, kind="dsa", layer=layer,
        rows=L if decode else S,              # decode: split-KV folding dim
        cols=H, batch=B,
        flops=(core + indexer) * tm,
        weight_bytes=arch.mla_weights() * BF16 + w_idx,
        act_bytes=4.0 * B * S * D * BF16,
        # the q and kv latents; per head the query, its latent form, the
        # latent output and the output; the selected positions (int32,
        # written and read)
        inner_bytes=B * S * (qr + kvr + dr + H * (dn + dr + kvr + dr + kvr
                                                  + dv)) * BF16
        + 2.0 * B * ksel * 4.0,
        state_bytes=caches if mode != "train" else 0.0,
        kv_bytes=caches,
        col_divisor=H,
        latent_dim=kvr,
        scan_group=_SCAN_GROUP["dsa"],
        collective_kind="tp_allreduce",
        train_multiplier=tm,
        weight_stream=(mode != "train"),
        internal_rows=decode,
        fm_width=D,
        indexer_flops=indexer * tm,
        indexer_weight_bytes=w_idx,
        state_io_bytes=io,
        index_topk=min(K, L),
    )


def _ffn_node(arch: ArchConfig, name: str, layer: int, B: int, S: int,
              tm: float, stream: bool, kind: str = "ffn",
              width: int = 0) -> Node:
    D, F = arch.d_model, width or arch.d_ff
    n = _n_ffn_mats(arch)
    return Node(
        name=name, kind=kind, layer=layer,
        rows=S, cols=F, batch=B,
        flops=2.0 * B * S * D * F * n * tm,
        weight_bytes=n * D * F * BF16,
        act_bytes=4.0 * B * S * D * BF16,
        inner_bytes=(n - 1) * B * S * F * BF16,
        col_divisor=F,
        scan_group=_SCAN_GROUP[kind],
        collective_kind="tp_allreduce",
        train_multiplier=tm,
        weight_stream=stream,
        fm_width=D,
    )


def _moe_node(arch: ArchConfig, name: str, layer: int, B: int, S: int,
              tm: float) -> Node:
    D, F = arch.d_model, arch.expert_d_ff
    E, K = arch.num_experts, arch.experts_per_token
    n = _n_ffn_mats(arch)
    tokens = B * S
    router_flops = 2.0 * tokens * D * E
    expert_flops = 2.0 * tokens * K * D * F * n
    wb = (E * n * D * F + D * E) * BF16
    touched = min(E, tokens * K)              # experts whose weights stream
    return Node(
        name=name, kind="moe", layer=layer,
        rows=S, cols=E, batch=B,
        flops=(router_flops + expert_flops) * tm,
        weight_bytes=wb,
        act_bytes=4.0 * B * S * D * BF16,
        inner_bytes=(touched * n * D * F * BF16   # touched expert weight reads
                     + tokens * K * (D + (n - 1) * F) * BF16),
        col_divisor=E,
        ep_topk=K,
        scan_group=_SCAN_GROUP["moe"],
        collective_kind="ep_alltoall",
        train_multiplier=tm,
        fm_width=D,
    )


def _ssm_node(arch: ArchConfig, name: str, layer: int, B: int, S: int,
              tm: float, mode: str) -> Node:
    D = arch.d_model
    di = arch.ssm_expand * D
    ds = arch.ssm_d_state
    dtr = max(1, D // 16)
    flops = (2.0 * B * S * D * 2 * di              # in_proj (x, z)
             + 2.0 * B * S * di * (dtr + 2 * ds)   # x_proj
             + 2.0 * B * S * dtr * di              # dt_proj
             + 2.0 * B * S * di * arch.ssm_conv    # depthwise conv
             + 9.0 * B * S * di * ds               # selective scan
             + 2.0 * B * S * di * D)               # out_proj
    wb = (D * 2 * di + di * (dtr + 2 * ds) + dtr * di + di * arch.ssm_conv
          + di * ds + 2 * di + di * D) * BF16
    state = B * di * ds * FP32 + B * di * arch.ssm_conv * BF16
    return Node(
        name=name, kind="ssm", layer=layer,
        rows=S, cols=di, batch=B,
        flops=flops * tm,
        weight_bytes=wb,
        act_bytes=4.0 * B * S * D * BF16,
        inner_bytes=3.0 * B * S * di * BF16,
        state_bytes=state if mode != "train" else 0.0,
        carry_bytes=B * di * ds * FP32,
        col_divisor=di,
        scan_group=_SCAN_GROUP["ssm"],
        collective_kind="tp_allreduce",
        train_multiplier=tm,
        weight_stream=(mode != "train"),
        fm_width=D,
    )


def _rwkv_tmix_node(arch: ArchConfig, name: str, layer: int, B: int, S: int,
                    tm: float, mode: str) -> Node:
    D = arch.d_model
    hs = arch.rwkv_head_size
    Hr = D // hs
    proj_flops = 2.0 * B * S * D * D * 5.0         # r,k,v,g,o
    wkv_flops = 6.0 * B * S * D * hs               # state update + readout
    wb = (5.0 * D * D + 2.0 * D + D * hs) * BF16   # + decay lora (approx)
    state = B * Hr * hs * hs * FP32
    return Node(
        name=name, kind="rwkv_tmix", layer=layer,
        rows=S, cols=Hr, batch=B,
        flops=(proj_flops + wkv_flops) * tm,
        weight_bytes=wb,
        act_bytes=4.0 * B * S * D * BF16,
        inner_bytes=3.0 * B * S * D * BF16,
        state_bytes=state if mode != "train" else 0.0,
        carry_bytes=B * Hr * hs * hs * FP32,
        col_divisor=Hr,
        scan_group=_SCAN_GROUP["rwkv_tmix"],
        collective_kind="tp_allreduce",
        train_multiplier=tm,
        weight_stream=(mode != "train"),
        fm_width=D,
    )


def _rwkv_cmix_node(arch: ArchConfig, name: str, layer: int, B: int, S: int,
                    tm: float, stream: bool) -> Node:
    D, F = arch.d_model, arch.d_ff
    flops = 2.0 * B * S * (D * F + F * D + D * D)  # k, v, receptance
    wb = (2.0 * D * F + D * D) * BF16
    return Node(
        name=name, kind="rwkv_cmix", layer=layer,
        rows=S, cols=F, batch=B,
        flops=flops * tm,
        weight_bytes=wb,
        act_bytes=4.0 * B * S * D * BF16,
        inner_bytes=B * S * F * BF16,
        col_divisor=F,
        scan_group=_SCAN_GROUP["rwkv_cmix"],
        collective_kind="tp_allreduce",
        train_multiplier=tm,
        weight_stream=stream,
        fm_width=D,
    )
