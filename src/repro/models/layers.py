"""Common layers: norms, initialisers, RoPE/M-RoPE, FFN.

Parameter-sharding roles (see core/exporter.py): every param dict here has a
matching entry in ``PARAM_ROLES[kind]`` so the exporter can emit
PartitionSpecs without inspecting the model.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# ----------------------------------------------------------------------
# sharding-role registry (kind -> param name -> role)
# ----------------------------------------------------------------------
PARAM_ROLES: Dict[str, Dict[str, str]] = {
    "embed": {"table": "table"},
    "head": {"w": "head"},
    "norm": {"scale": "replicate", "bias": "replicate"},
    "attn": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "wq": "col", "wk": "col", "wv": "col", "wo": "row",
    },
    "cross_attn": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "wq": "col", "wk": "col", "wv": "col", "wo": "row",
    },
    "enc_attn": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "wq": "col", "wk": "col", "wv": "col", "wo": "row",
    },
    "ffn": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "w_gate": "col", "w_up": "col", "w_down": "row",
    },
    "enc_ffn": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "w_gate": "col", "w_up": "col", "w_down": "row",
    },
    "moe": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "router": "replicate",
        "w_gate": "expert", "w_up": "expert", "w_down": "expert",
    },
    # the shared expert's weights live in the moe block's params (it reads
    # the moe's normed input) and shard by the shared_expert node's plan
    "shared_expert": {
        "shared_w_gate": "col", "shared_w_up": "col", "shared_w_down": "row",
    },
    "mla": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "wq_a": "replicate", "q_norm": "replicate", "wq_b": "col",
        "wkv_a": "replicate", "kv_norm": "replicate", "wkv_b": "col",
        "wo": "row",
    },
    # the indexer is whole on every head shard (graph_builder._dsa_node)
    "dsa": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "wq_a": "replicate", "q_norm": "replicate", "wq_b": "col",
        "wkv_a": "replicate", "kv_norm": "replicate", "wkv_b": "col",
        "wo": "row", "wq_idx": "replicate", "wk_idx": "replicate",
        "k_idx_scale": "replicate", "k_idx_bias": "replicate",
        "w_idx": "replicate",
    },
    "ssm": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "in_proj": "col", "conv_w": "expert", "conv_b": "expert",
        "x_proj": "row", "dt_proj": "col", "dt_bias": "expert",
        "a_log": "expert", "d_skip": "expert", "out_proj": "row",
    },
    "rwkv_tmix": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "mix_r": "replicate", "mix_k": "replicate", "mix_v": "replicate",
        "mix_g": "replicate", "mix_w": "replicate",
        "wr": "col", "wk": "col", "wv": "col", "wg": "col", "wo": "row",
        "decay": "expert", "bonus": "expert",
    },
    "rwkv_cmix": {
        "ln_scale": "replicate", "ln_bias": "replicate",
        "mix_k": "replicate", "mix_r": "replicate",
        "wk": "col", "wv": "row", "wr": "replicate",
    },
}


# ----------------------------------------------------------------------
# initialisers
# ----------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


# ----------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------

def init_norm(d: int, kind: str, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "ln":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(x: jax.Array, scale: jax.Array, bias: Optional[jax.Array],
               kind: str, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def block_norm(x: jax.Array, params: Dict[str, jax.Array], kind: str) -> jax.Array:
    return apply_norm(x, params["ln_scale"], params.get("ln_bias"), kind)


# ----------------------------------------------------------------------
# RoPE / M-RoPE
# ----------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                        # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions_3d: jax.Array, theta: float) -> jax.Array:
    """Qwen2-VL multimodal RoPE. positions_3d: (3, B, S) for (t, h, w);
    head_dim is split into three contiguous sections rotated by its own
    position stream (temporal gets half, spatial a quarter each)."""
    dh = x.shape[-1]
    s_t, s_h = dh // 2, dh // 4
    sections = [s_t, s_h, dh - s_t - s_h]
    outs = []
    start = 0
    for sec, pos in zip(sections, positions_3d):
        xs = jax.lax.dynamic_slice_in_dim(x, start, sec, axis=-1)
        outs.append(apply_rope(xs, pos, theta))
        start += sec
    return jnp.concatenate(outs, axis=-1)


# ----------------------------------------------------------------------
# FFN
# ----------------------------------------------------------------------

def init_ffn(key, d_model: int, d_ff: int, act: str, norm: str,
             dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[1], d_model, d_ff, dtype),
         "w_down": dense_init(ks[2], d_ff, d_model, dtype)}
    if act == "swiglu":
        p["w_gate"] = dense_init(ks[0], d_model, d_ff, dtype)
    p.update({f"ln_{k}": v for k, v in init_norm(d_model, norm, dtype).items()})
    return p


def ffn_inner(p: Dict[str, jax.Array], h: jax.Array, act: str, dtype,
              shard_fn=lambda a, role=None: a) -> jax.Array:
    """The FFN on an already-normed input, without the residual."""
    up = h @ p["w_up"]
    if act == "swiglu":
        inner = jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32)).astype(dtype) * up
    elif act == "gelu":
        inner = jax.nn.gelu(up.astype(jnp.float32)).astype(dtype)
    else:  # relu_sq
        inner = jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(dtype)
    inner = shard_fn(inner, role="inner")
    return inner @ p["w_down"]


def apply_ffn(x: jax.Array, p: Dict[str, jax.Array], act: str, norm: str,
              shard_fn=lambda a, role=None: a) -> jax.Array:
    h = block_norm(x, p, norm)
    out = ffn_inner(p, h, act, x.dtype, shard_fn)
    return x + shard_fn(out, role="boundary")
