"""GQA attention (self / cross / encoder) with optional KV cache,
multi-head latent attention (MLA) with a latent cache, and DeepSeek sparse
attention (DSA): latent attention over the positions a lightning indexer
selects, with a latent cache and an index-key cache.

The scaled-dot-product core dispatches to the Pallas flash-attention kernel
(kernels/ops.py) when enabled, else to the pure-jnp oracle (kernels/ref.py) —
the oracle is what XLA compiles in the CPU dry-run.
"""
from __future__ import annotations

import math
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


# ----------------------------------------------------------------------
# DeepSeek sparse attention (DeepSeek-V3.2)
# ----------------------------------------------------------------------

def init_dsa(key, d_model: int, num_heads: int, q_lora_rank: int,
             kv_lora_rank: int, qk_nope_head_dim: int, qk_rope_head_dim: int,
             v_head_dim: int, index_n_heads: int, index_head_dim: int,
             norm: str, dtype=jnp.bfloat16):
    """Latent attention's parameters and the lightning indexer's: the
    query projection from the q latent, one key projection with its
    LayerNorm, and the per-head weights (``ArchConfig.indexer_weights``)."""
    k_mla, k_idx = jax.random.split(key)
    p = init_mla(k_mla, d_model, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim, norm, dtype)
    ks = jax.random.split(k_idx, 3)
    p.update({
        "wq_idx": dense_init(ks[0], q_lora_rank,
                             index_n_heads * index_head_dim, dtype),
        "wk_idx": dense_init(ks[1], d_model, index_head_dim, dtype),
        "k_idx_scale": jnp.ones((index_head_dim,), dtype),
        "k_idx_bias": jnp.zeros((index_head_dim,), dtype),
        "w_idx": dense_init(ks[2], d_model, index_n_heads, dtype),
    })
    return p


def _pow2(n: int) -> bool:
    return n > 0 and n & (n - 1) == 0


def _causal_scores(q, k):
    """s[b, t, h, u] = q[b, t, h] . k[b, u] for u <= t, 0 above.

    For n a power of two only the lower triangle is computed: the
    diagonal, then at each level the n / 2m squares of side m that lie
    just below it (queries [(2g+1)m, (2g+2)m) against keys [2gm,
    (2g+1)m)), n (n + 1) / 2 products a head in all. Otherwise the whole
    square (the caller masks it)."""
    B, n, Hh, d = q.shape
    if not _pow2(n):
        return jnp.einsum("bthd,bud->bthu", q, k)
    i = jnp.arange(n)
    s = jnp.zeros((B, n, Hh, n), jnp.result_type(q, k))
    s = s.at[:, i, :, i].set(jnp.einsum("bthd,btd->tbh", q, k))
    m = n // 2
    while m:
        g = n // (2 * m)
        gi = jnp.arange(g)
        blk = jnp.einsum("bgqhd,bgkd->gbqhk",
                         q.reshape(B, g, 2, m, Hh, d)[:, :, 1],
                         k.reshape(B, g, 2, m, d)[:, :, 0])
        s = s.reshape(B, g, 2, m, Hh, g, 2, m).at[
            :, gi, 1, :, :, gi, 0, :].set(blk).reshape(B, n, Hh, n)
        m //= 2
    return s


def _causal_weighted_sum(a, v):
    """o[b, t, h] = sum over u <= t of a[b, t, h, u] v[b, u], over the
    blocks ``_causal_scores`` computes (``a`` is 0 above the diagonal)."""
    B, n, Hh, _ = a.shape
    d = v.shape[-1]
    if not _pow2(n):
        return jnp.einsum("bthu,bud->bthd", a, v)
    i = jnp.arange(n)
    diag = a[:, i, :, i].transpose(1, 0, 2)[..., None]        # (B, n, Hh, 1)
    # the diagonal's outer product, as a contraction of length one
    o = jax.lax.dot_general(diag, v[:, :, None, :].astype(a.dtype),
                            (((3,), (2,)), ((0, 1), (0, 1))))
    m = n // 2
    while m:
        g = n // (2 * m)
        gi = jnp.arange(g)
        blk = a.reshape(B, g, 2, m, Hh, g, 2, m)[:, gi, 1, :, :, gi, 0, :]
        ob = jnp.einsum("gbqhk,bgkd->bgqhd", blk,
                        v.reshape(B, g, 2, m, d)[:, :, 0])
        o = o.reshape(B, g, 2, m, Hh, d).at[:, :, 1].add(ob).reshape(
            B, n, Hh, d)
        m //= 2
    return o


def _softmax(s, keep):
    p = jax.nn.softmax(jnp.where(keep, s.astype(jnp.float32), -jnp.inf),
                       axis=-1)
    return p.astype(s.dtype)


def _dsa_inputs(x, p, *, num_heads, qk_nope_head_dim, qk_rope_head_dim,
                index_n_heads, index_head_dim, norm, positions, rope_theta):
    """The block's projections: latent attention's query (absorbed into
    the latent), latents and rotary parts, and the indexer's query, key
    and head weights."""
    B, S, _ = x.shape
    H, dn, dr = num_heads, qk_nope_head_dim, qk_rope_head_dim
    h = block_norm(x, p, norm)
    c_q = apply_norm(h @ p["wq_a"], p["q_norm"], None, "rms")
    q = (c_q @ p["wq_b"]).reshape(B, S, H, dn + dr)
    kv_a = h @ p["wkv_a"]
    c_kv = apply_norm(kv_a[..., :-dr], p["kv_norm"], None, "rms")
    wkv_b = p["wkv_b"].reshape(c_kv.shape[-1], H, -1)
    return {
        "q_lat": jnp.einsum("bshd,khd->bshk", q[..., :dn], wkv_b[..., :dn]),
        "q_rope": apply_rope(q[..., dn:], positions, rope_theta),
        "c_kv": c_kv,
        "k_rope": apply_rope(kv_a[..., None, -dr:], positions,
                             rope_theta)[:, :, 0],
        "q_idx": (c_q @ p["wq_idx"]).reshape(B, S, index_n_heads,
                                             index_head_dim),
        "k_idx": apply_norm(h @ p["wk_idx"], p["k_idx_scale"],
                            p["k_idx_bias"], "ln"),
        "w_idx": h @ p["w_idx"],                                # (B, S, HI)
        "w_uv": wkv_b[..., dn:],
    }


def _index_scores(w_idx, s):
    """I(t, s) = sum_j w(t, j) ReLU(s_j): the indexer's head weighting of
    its per-head dot products ``s`` (B, S, HI, L)."""
    return jnp.sum(w_idx[..., None] * jax.nn.relu(s), axis=2)


def _prefill_index(z):
    """A prefill's indexer scores over the causal triangle, -inf above."""
    S = z["k_idx"].shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    return jnp.where(causal, _index_scores(
        z["w_idx"], _causal_scores(z["q_idx"], z["k_idx"])), -jnp.inf)


def dsa_prefill_selection(x, p, *, index_topk: int, **kw):
    """The positions each query of a prefill from position 0 attends to
    (``attend_dsa`` without a cache): (B, S, min(K, S)) int32, query t's
    min(K, t + 1) positions first and -1 after them."""
    z = _dsa_inputs(x, p, **kw)
    S = x.shape[1]
    T = min(index_topk, S)
    t = jnp.arange(S)[:, None]
    first = jnp.where(jnp.arange(T)[None, :] <= t, jnp.arange(T)[None, :],
                      -1)
    sel = jnp.broadcast_to(first, (x.shape[0], S, T))
    if S > T:
        _, top = jax.lax.top_k(_prefill_index(z)[:, T:], index_topk)
        sel = jnp.concatenate([sel[:, :T], top], axis=1)
    return sel


def attend_dsa(x: jax.Array, p: Dict[str, jax.Array], *, num_heads: int,
               qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int,
               index_n_heads: int, index_head_dim: int, index_topk: int,
               norm: str, positions: jax.Array, rope_theta: float = 10000.0,
               cache: Optional[Dict[str, jax.Array]] = None,
               cache_pos: Optional[jax.Array] = None,
               shard_fn=lambda a, role=None: a):
    """One DeepSeek-sparse-attention block with pre-norm and residual
    (``graph_builder._dsa_node`` prices it).

    Indexer: I(t, s) = sum_j w(t, j) ReLU(qI(t, j) . kI(s)), with qI from
    the q latent, kI a LayerNorm'd projection of the normed input (one
    vector a token for all indexer heads) and w a projection of it. Query
    t attends to the top min(K, t + 1) positions s <= t by I(t, s), ties
    to the lower position (``lax.top_k``), K = ``index_topk``. The core is
    latent attention in the absorbed form: q_nope folds into the latent
    through the key half of ``wkv_b``, scores and the weighted sum run
    over the selected latent rows (c_kv and k_rope, shared by all heads),
    and the value half of ``wkv_b`` maps the result out of the latent.

    Without a cache the step is a prefill from position 0: the indexer
    scores the causal triangle and queries t < K attend to all of s <= t,
    both over the triangle only (``_causal_scores``); queries t >= K
    gather their K selected rows (``dsa_prefill_selection``). With
    ``cache`` and ``cache_pos`` this step's latents and index keys
    (``c_kv``, ``k_rope``, ``index_keys``) are written there, and every
    query scores the whole cache (positions past its own masked) and
    gathers min(K, cache length) rows.

    Departures from the published model, all elementwise: the indexer's
    query and key carry no rotary part, no Hadamard rotation, no FP8
    rounding and no scale on w. Returns (y, new_cache)."""
    B, S, _ = x.shape
    H, dv, K = num_heads, v_head_dim, index_topk
    z = _dsa_inputs(x, p, num_heads=H, qk_nope_head_dim=qk_nope_head_dim,
                    qk_rope_head_dim=qk_rope_head_dim,
                    index_n_heads=index_n_heads,
                    index_head_dim=index_head_dim, norm=norm,
                    positions=positions, rope_theta=rope_theta)
    q_lat, q_rope = shard_fn(z["q_lat"], role="heads"), z["q_rope"]
    scale = 1.0 / math.sqrt(qk_nope_head_dim + qk_rope_head_dim)

    def attend_selected(q_l, q_r, sel, ok, rows, rope_rows):
        """The core over the gathered rows ``sel`` (B, T, k) of
        ``rows``/``rope_rows``; ``ok`` masks selections past the query."""
        c_sel = jax.vmap(lambda r, i: r[i])(rows, sel)         # (B, T, k, kvr)
        r_sel = jax.vmap(lambda r, i: r[i])(rope_rows, sel)
        s = (jnp.einsum("bthk,btjk->bthj", q_l, c_sel)
             + jnp.einsum("bthr,btjr->bthj", q_r, r_sel)) * scale
        a = _softmax(s, ok[:, :, None, :])
        return jnp.einsum("bthj,btjk->bthk", a, c_sel)

    new_cache = cache
    if cache is not None and cache_pos is not None:
        def put(name, val):
            return jax.lax.dynamic_update_slice_in_dim(
                cache[name], val.astype(cache[name].dtype), cache_pos, axis=1)
        new_cache = {"c_kv": put("c_kv", z["c_kv"]),
                     "k_rope": put("k_rope", z["k_rope"]),
                     "index_keys": put("index_keys", z["k_idx"])}
        rows = new_cache["c_kv"].astype(x.dtype)
        L = rows.shape[1]
        valid = jnp.arange(L)[None, None, :] <= positions[:, :, None]
        I = jnp.where(valid, _index_scores(z["w_idx"], jnp.einsum(
            "bshd,bld->bshl", z["q_idx"],
            new_cache["index_keys"].astype(x.dtype))), -jnp.inf)
        _, sel = jax.lax.top_k(I, min(K, L))
        o_lat = attend_selected(q_lat, q_rope, sel,
                                jnp.take_along_axis(valid, sel, axis=-1),
                                rows, new_cache["k_rope"].astype(x.dtype))
    else:
        c_kv, k_rope = z["c_kv"], z["k_rope"]
        T = min(K, S)
        # queries t < K attend to every position s <= t
        s = (_causal_scores(q_lat[:, :T], c_kv[:, :T])
             + _causal_scores(q_rope[:, :T], k_rope[:, :T])) * scale
        a = _softmax(s, jnp.tril(jnp.ones((T, T), bool))[:, None, :])
        o_lat = _causal_weighted_sum(a, c_kv[:, :T])
        if S > T:
            _, sel = jax.lax.top_k(_prefill_index(z)[:, T:], K)
            o_lat = jnp.concatenate([o_lat, attend_selected(
                q_lat[:, T:], q_rope[:, T:], sel, jnp.ones(sel.shape, bool),
                c_kv, k_rope)], axis=1)
    o = jnp.einsum("bshk,khd->bshd", o_lat, z["w_uv"])
    y = o.reshape(B, S, H * dv) @ p["wo"]
    return x + shard_fn(y, role="boundary"), new_cache
