"""GQA attention (self / cross / encoder) with optional KV cache, and
multi-head latent attention (MLA) with a latent cache.

The scaled-dot-product core dispatches to the Pallas flash-attention kernel
(kernels/ops.py) when enabled, else to the pure-jnp oracle (kernels/ref.py) —
the oracle is what XLA compiles in the CPU dry-run.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_mrope, apply_norm, apply_rope,
                                 block_norm, dense_init, init_norm)


def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, norm: str, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }
    p.update({f"ln_{k}": v for k, v in init_norm(d_model, norm, dtype).items()})
    return p


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
         q_offset: int = 0, impl: str = "ref") -> jax.Array:
    """q: (B,Sq,H,dh) k,v: (B,Skv,Hkv,dh) -> (B,Sq,H,dh).

    impl: ref     — naive S x S softmax (oracle; O(S^2) memory)
          chunked — online-softmax over KV blocks in pure jnp (XLA path
                    with flash memory behaviour; what the dry-run lowers)
          flash   — Pallas TPU kernel (interpret-mode on CPU)
    """
    if impl == "flash":
        from repro.kernels import ops
        return ops.flash_attention(q, k, v, causal=causal, q_offset=q_offset)
    if impl == "chunked":
        from repro.kernels import ref
        return ref.attention_chunked(q, k, v, causal=causal,
                                     q_offset=q_offset)
    from repro.kernels import ref
    return ref.attention(q, k, v, causal=causal, q_offset=q_offset)


def attend(x: jax.Array, p: Dict[str, jax.Array], *,
           num_heads: int, num_kv_heads: int, head_dim: int,
           norm: str, causal: bool = True,
           positions: Optional[jax.Array] = None,
           rope_theta: float = 10000.0,
           mrope_positions: Optional[jax.Array] = None,
           kv_src: Optional[jax.Array] = None,
           cache: Optional[Dict[str, jax.Array]] = None,
           cache_pos: Optional[jax.Array] = None,
           write_cross: bool = False,
           attn_impl: str = "ref",
           shard_fn=lambda a, role=None: a):
    """One attention block with pre-norm and residual.

    kv_src     cross-attention source (encoder output); None => self-attn.
    cache      {"k","v"}: (B, L, Hkv, dh) decode caches. With cache_pos given,
               new K/V are written at that position (decode step).
    write_cross  prefill: (re)compute the cross-attention KV from kv_src and
               store it; decode reads the stored cache instead.
    Returns (y, new_cache).
    """
    B, Sq, D = x.shape
    h = block_norm(x, p, norm)
    src = kv_src if kv_src is not None else h

    q = (h @ p["wq"]).reshape(B, Sq, num_heads, head_dim)
    if cache is not None and kv_src is not None and not write_cross:
        # cross-attention with precomputed encoder KV cache
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        k = (src @ p["wk"]).reshape(B, src.shape[1], num_kv_heads, head_dim)
        v = (src @ p["wv"]).reshape(B, src.shape[1], num_kv_heads, head_dim)
        if kv_src is None and positions is not None:
            if mrope_positions is not None:
                q = apply_mrope(q, mrope_positions, rope_theta)
                k = apply_mrope(k, mrope_positions[:, :, :src.shape[1]]
                                if mrope_positions.shape[-1] != src.shape[1]
                                else mrope_positions, rope_theta)
            else:
                q = apply_rope(q, positions, rope_theta)
                k = apply_rope(k, positions[:, :src.shape[1]]
                               if positions.shape[-1] != src.shape[1]
                               else positions, rope_theta)
        new_cache = cache
        if cache is not None and kv_src is None and cache_pos is not None:
            # prefill/decode: insert this step's K/V at cache_pos
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_pos, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_pos, axis=1)
            new_cache = {"k": k_cache, "v": v_cache}
            k, v = k_cache, v_cache
        elif cache is not None:
            new_cache = {"k": k.astype(cache["k"].dtype),
                         "v": v.astype(cache["v"].dtype)}

    q = shard_fn(q, role="heads")
    q_offset = cache_pos if cache_pos is not None else 0
    o = sdpa(q, k, v, causal=causal and kv_src is None, q_offset=q_offset,
             impl=attn_impl)
    o = o.reshape(B, Sq, num_heads * head_dim)
    y = o @ p["wo"]
    return x + shard_fn(y, role="boundary"), new_cache


# ----------------------------------------------------------------------
# multi-head latent attention (Kimi-K2 / DeepSeek-V3)
# ----------------------------------------------------------------------

def init_mla(key, d_model: int, num_heads: int, q_lora_rank: int,
             kv_lora_rank: int, qk_nope_head_dim: int, qk_rope_head_dim: int,
             v_head_dim: int, norm: str, dtype=jnp.bfloat16):
    H, dn, dr = num_heads, qk_nope_head_dim, qk_rope_head_dim
    ks = jax.random.split(key, 5)
    p = {
        "wq_a": dense_init(ks[0], d_model, q_lora_rank, dtype),
        "q_norm": jnp.ones((q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], q_lora_rank, H * (dn + dr), dtype),
        "wkv_a": dense_init(ks[2], d_model, kv_lora_rank + dr, dtype),
        "kv_norm": jnp.ones((kv_lora_rank,), dtype),
        "wkv_b": dense_init(ks[3], kv_lora_rank, H * (dn + v_head_dim), dtype),
        "wo": dense_init(ks[4], H * v_head_dim, d_model, dtype),
    }
    p.update({f"ln_{k}": v for k, v in init_norm(d_model, norm, dtype).items()})
    return p


def attend_mla(x: jax.Array, p: Dict[str, jax.Array], *, num_heads: int,
               qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int,
               norm: str, positions: jax.Array, rope_theta: float = 10000.0,
               cache: Optional[Dict[str, jax.Array]] = None,
               cache_pos: Optional[jax.Array] = None,
               attn_impl: str = "ref",
               shard_fn=lambda a, role=None: a):
    """One latent-attention block with pre-norm and residual, in the
    non-absorbed form: queries come from the q latent; the kv latent
    ``c_kv`` is expanded to per-head ``k_nope`` and ``v``, and one rotary
    key ``k_rope`` is shared by all heads. The cache holds ``c_kv``
    (B, L, kv_lora_rank) and ``k_rope`` (B, L, qk_rope_head_dim): with
    ``cache_pos`` this step's latents are written there and attention
    runs over the whole cache. Returns (y, new_cache)."""
    B, S, _ = x.shape
    H, dn, dr, dv = num_heads, qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    h = block_norm(x, p, norm)
    c_q = apply_norm(h @ p["wq_a"], p["q_norm"], None, "rms")
    q = (c_q @ p["wq_b"]).reshape(B, S, H, dn + dr)
    q_rope = apply_rope(q[..., dn:], positions, rope_theta)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    kv_a = h @ p["wkv_a"]
    c_kv = apply_norm(kv_a[..., :-dr], p["kv_norm"], None, "rms")
    k_rope = apply_rope(kv_a[..., None, -dr:], positions, rope_theta)[:, :, 0]
    new_cache = cache
    if cache is not None and cache_pos is not None:
        c_kv = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_pos, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), cache_pos,
            axis=1)
        new_cache = {"c_kv": c_kv, "k_rope": k_rope}
    L = c_kv.shape[1]
    kv = (c_kv.astype(x.dtype) @ p["wkv_b"]).reshape(B, L, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope.astype(x.dtype)[:, :, None],
                                        (B, L, H, dr))], axis=-1)
    v = kv[..., dn:]
    q = shard_fn(q, role="heads")
    o = sdpa(q, k, v, causal=True,
             q_offset=cache_pos if cache_pos is not None else 0,
             impl=attn_impl)
    y = o.reshape(B, S, H * dv) @ p["wo"]
    return x + shard_fn(y, role="boundary"), new_cache
