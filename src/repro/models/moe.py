"""Mixture-of-Experts FFN: top-k routing with sort-based dispatch.

Sort-based (gather/scatter) dispatch keeps compiled FLOPs proportional to
E x capacity x D x F (the true expert work) instead of the tokens x E
one-hot-einsum blow-up — essential for honest rooflines. Expert weights carry
the leading experts dim (sharding role "expert" -> EP over cols_axes); GSPMD
lowers the token exchange to an all-to-all when experts are sharded.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.layers import (block_norm, dense_init, ffn_inner, init_ffn,
                                 init_norm)


def init_moe(key, d_model: int, d_ff: int, num_experts: int, act: str,
             norm: str, dtype=jnp.bfloat16,
             shared_d_ff: int = 0) -> Dict[str, jax.Array]:
    """``shared_d_ff`` > 0 adds an always-on shared expert of that width
    (``n_shared_experts * moe_intermediate_size``)."""
    ks = jax.random.split(key, 5)
    E = num_experts
    def ed(k, a, b):
        return jax.vmap(lambda kk: dense_init(kk, a, b, dtype))(
            jax.random.split(k, E))
    p = {
        "router": dense_init(ks[0], d_model, E, jnp.float32),
        "w_up": ed(ks[1], d_model, d_ff),
        "w_down": ed(ks[2], d_ff, d_model),
    }
    if act == "swiglu":
        p["w_gate"] = ed(ks[3], d_model, d_ff)
    if shared_d_ff:
        p.update({f"shared_{k}": v for k, v in init_ffn(
            ks[4], d_model, shared_d_ff, act, norm, dtype).items()
            if not k.startswith("ln_")})
    p.update({f"ln_{k}": v for k, v in init_norm(d_model, norm, dtype).items()})
    return p


def apply_moe(x: jax.Array, p: Dict[str, jax.Array], *, top_k: int, act: str,
              norm: str, capacity_factor: float = 1.25,
              shard_fn=lambda a, role=None: a) -> jax.Array:
    """x: (B, S, D) -> (B, S, D) with residual."""
    B, S, D = x.shape
    E = p["router"].shape[-1]
    h = block_norm(x, p, norm)
    tokens = h.reshape(B * S, D)
    T = B * S

    logits = tokens.astype(jnp.float32) @ p["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)        # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # flatten (token, k) assignments and sort by expert id
    flat_expert = expert_ids.reshape(-1)                       # (T*K,)
    flat_token = jnp.repeat(jnp.arange(T), top_k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]

    # per-expert capacity slots; overflowing assignments are dropped
    cap = max(1, int(capacity_factor * T * top_k / E))
    # position of each assignment within its expert's run
    ranks = _rank_in_group(sorted_expert, E)
    keep = ranks < cap
    slot = jnp.where(keep, sorted_expert * cap + ranks, E * cap)  # overflow sink

    # gather tokens into (E*cap, D) buffers (one padded sink row)
    buf = jnp.zeros((E * cap + 1, D), tokens.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None],
                                     tokens[sorted_token], 0.0))
    xe = buf[:-1].reshape(E, cap, D)
    xe = shard_fn(xe, role="experts")

    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    if act == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
        inner = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        inner = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    ye = jnp.einsum("ecf,efd->ecd", inner, p["w_down"])
    ye = shard_fn(ye, role="experts")

    # scatter back, weighted by the gates
    ye_flat = jnp.concatenate([ye.reshape(E * cap, D),
                               jnp.zeros((1, D), ye.dtype)], axis=0)
    contrib = ye_flat[jnp.where(keep, slot, E * cap)]          # (T*K, D)
    contrib = contrib * sorted_gate[:, None].astype(contrib.dtype)
    out = jnp.zeros((T, D), x.dtype).at[sorted_token].add(contrib)
    out = out.reshape(B, S, D)
    if "shared_w_up" in p:
        # the shared expert sees every token, from the same normed input
        out = out + ffn_inner({k[len("shared_"):]: v for k, v in p.items()
                               if k.startswith("shared_")}, h, act, x.dtype)
    return x + shard_fn(out, role="boundary")


def _rank_in_group(sorted_ids: jax.Array, num_groups: int) -> jax.Array:
    """Rank of each element within its (sorted) group, O(n) via segment scan."""
    T = sorted_ids.shape[0]
    ones = jnp.ones_like(sorted_ids)
    # cumulative count per group id using a one-hot-free segment trick:
    # rank[i] = i - first_index_of_group(sorted_ids[i])
    idx = jnp.arange(T)
    is_start = jnp.concatenate([jnp.array([True]),
                                sorted_ids[1:] != sorted_ids[:-1]])
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, 0))
    return idx - group_start
