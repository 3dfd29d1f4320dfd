"""Composable model zoo: one builder for every registered architecture.

A model is a chain of *segments*; each segment is a homogeneous stack of
layer-groups executed with ``lax.scan`` over stacked parameters (keeps the
HLO small — one CPU core compiles 80-layer models with 512 fake devices).
A layer-group is a static *pattern* of block kinds, e.g.:

  dense llama     ("attn", "ffn") x num_layers
  jamba           ("ssm","ffn","ssm","moe",... ,"attn","moe") x 9   (1:7, MoE alt)
  kimi-k2         ("mla","ffn") x 1  +  ("mla","moe") x 60  (moe with a
                  shared expert)
  deepseek-v3.2   ("dsa","ffn") x 1 (three times)  +  ("dsa","moe") x 58
  rwkv6           ("rwkv_tmix","rwkv_cmix") x 24
  whisper         enc: ("enc_attn","enc_ffn") x 12;
                  dec: ("attn","cross_attn","ffn") x 12

Parameters are pytrees of jnp arrays; ``param_shapes``/``param_specs`` give
ShapeDtypeStructs and PartitionSpecs for the dry-run without allocating.

Batch dict: tokens (B,S) int32 [+ labels; positions; mrope_positions (3,B,S);
frames (B,F,D) for the stubbed audio frontend].
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rwkv as R
from repro.models import ssm as S


@dataclass(frozen=True)
class Segment:
    name: str
    pattern: Tuple[str, ...]        # block kinds per layer-group
    count: int                      # scan length
    layer_of: Tuple[int, ...]       # global layer index offset of each pattern pos
    encoder: bool = False


def build_segments(arch: ArchConfig,
                   layer_range: Optional[Tuple[int, int]] = None) -> List[Segment]:
    lo, hi = layer_range if layer_range else (0, arch.num_layers)
    segs: List[Segment] = []

    if arch.encoder_layers and (layer_range is None or lo == 0):
        segs.append(Segment("enc", ("enc_attn", "enc_ffn"),
                            arch.encoder_layers,
                            (0, 0), encoder=True))

    def block_pattern(i: int) -> Tuple[str, ...]:
        kinds = []
        mixer = arch.layer_kind(i)
        if mixer == "attn":
            kinds.append("attn")
            if arch.cross_attention:
                kinds.append("cross_attn")
        elif mixer in ("mla", "dsa"):
            kinds.append(mixer)
        elif mixer == "ssm":
            kinds.append("ssm")
        else:
            kinds.append("rwkv_tmix")
        fk = arch.ffn_kind(i)
        if mixer == "rwkv":
            kinds.append("rwkv_cmix")
        else:
            kinds.append(fk)
        return tuple(kinds)

    # group layers into runs with a repeating pattern of period `attn_period`
    period = max(arch.attn_period, 1)
    i = lo
    while i < hi:
        if i < arch.first_k_dense:
            segs.append(Segment(f"dec{i}", block_pattern(i), 1, (0,)))
            i += 1
            continue
        # find the maximal run starting at i where pattern repeats with
        # period `period` (jamba needs i aligned to the period)
        if period > 1 and i % period != 0:
            run = period - (i % period)
            run = min(run, hi - i)
        else:
            run = hi - i
            if period > 1:
                run -= run % period
                if run == 0:
                    run = hi - i
        group = min(period, run) if period > 1 else 1
        n_groups = max(1, run // group)
        pattern: Tuple[str, ...] = ()
        layer_of: Tuple[int, ...] = ()
        for j in range(group):
            pat = block_pattern(i + j)
            pattern += pat
            layer_of += (j,) * len(pat)
        segs.append(Segment(f"dec{i}", pattern, n_groups, layer_of))
        i += group * n_groups
    return segs


_INIT = {
    "attn": lambda key, arch: A.init_attention(
        key, arch.d_model, arch.num_heads, arch.num_kv_heads, arch.head_dim,
        arch.norm),
    "cross_attn": lambda key, arch: A.init_attention(
        key, arch.d_model, arch.num_heads, arch.num_kv_heads, arch.head_dim,
        arch.norm),
    "enc_attn": lambda key, arch: A.init_attention(
        key, arch.d_model, arch.num_heads, arch.num_kv_heads, arch.head_dim,
        arch.norm),
    "ffn": lambda key, arch: L.init_ffn(key, arch.d_model, arch.d_ff,
                                        arch.act, arch.norm),
    "enc_ffn": lambda key, arch: L.init_ffn(key, arch.d_model, arch.d_ff,
                                            "gelu" if arch.act == "gelu" else arch.act,
                                            arch.norm),
    "mla": lambda key, arch: A.init_mla(
        key, arch.d_model, arch.num_heads, arch.q_lora_rank,
        arch.kv_lora_rank, arch.qk_nope_head_dim, arch.qk_rope_head_dim,
        arch.v_head_dim, arch.norm),
    "dsa": lambda key, arch: A.init_dsa(
        key, arch.d_model, arch.num_heads, arch.q_lora_rank,
        arch.kv_lora_rank, arch.qk_nope_head_dim, arch.qk_rope_head_dim,
        arch.v_head_dim, arch.index_n_heads, arch.index_head_dim, arch.norm),
    "moe": lambda key, arch: M.init_moe(
        key, arch.d_model, arch.expert_d_ff, arch.num_experts, arch.act,
        arch.norm, shared_d_ff=arch.n_shared_experts * arch.expert_d_ff),
    "ssm": lambda key, arch: S.init_ssm(key, arch.d_model, arch.ssm_expand,
                                        arch.ssm_d_state, arch.ssm_conv,
                                        arch.norm),
    "rwkv_tmix": lambda key, arch: R.init_rwkv_tmix(key, arch.d_model,
                                                    arch.rwkv_head_size,
                                                    arch.norm),
    "rwkv_cmix": lambda key, arch: R.init_rwkv_cmix(key, arch.d_model,
                                                    arch.d_ff, arch.norm),
}


class Model:
    def __init__(self, arch: ArchConfig,
                 layer_range: Optional[Tuple[int, int]] = None,
                 include_embed: bool = True, include_head: bool = True,
                 use_flash: bool = False, remat: bool = True,
                 unroll: bool = False, attn_impl: Optional[str] = None):
        self.arch = arch
        self.segments = build_segments(arch, layer_range)
        self.include_embed = include_embed
        self.include_head = include_head
        self.use_flash = use_flash
        # attention implementation: ref | chunked | flash (see models/attention.sdpa)
        self.attn_impl = attn_impl or ("flash" if use_flash else "ref")
        self.remat = remat
        # unroll=True inlines every scan iteration: compile is slower but
        # XLA cost_analysis becomes exact (while bodies are counted once
        # regardless of trip count) — used by the roofline extrapolation.
        self.unroll = unroll

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------
    def init_params(self, key: jax.Array) -> Dict[str, Any]:
        arch = self.arch
        params: Dict[str, Any] = {}
        key, k_embed, k_head = jax.random.split(key, 3)
        need_embed = self.include_embed or (self.include_head
                                            and arch.tie_embeddings)
        if need_embed:
            params["embed"] = {"table": L.dense_init(
                k_embed, arch.vocab_size, arch.d_model).astype(jnp.bfloat16)}
        for seg in self.segments:
            key, sub = jax.random.split(key)
            pos_keys = jax.random.split(sub, len(seg.pattern))
            seg_params = {}
            for j, kind in enumerate(seg.pattern):
                stack_keys = jax.random.split(pos_keys[j], seg.count)
                seg_params[f"p{j}_{kind}"] = jax.vmap(
                    lambda kk: _INIT[kind](kk, arch))(stack_keys)
            params[seg.name] = seg_params
        if self.include_head:
            params["final_norm"] = {
                f"ln_{k}": v
                for k, v in L.init_norm(arch.d_model, arch.norm).items()}
            if not arch.tie_embeddings:
                params["head"] = {"w": L.dense_init(
                    k_head, arch.d_model, arch.vocab_size)}
        return params

    def param_shapes(self, key: Optional[jax.Array] = None):
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self.init_params, key)

    def param_specs(self, plan, partition: int = 0):
        """PartitionSpec pytree mirroring init_params, from a ShardingPlan."""
        shapes = self.param_shapes()

        def spec(path: Tuple[str, ...], leaf):
            top = path[0]
            name = path[-1]
            if top == "embed":
                return plan.spec_for_role("table", leaf.ndim, "embed", partition)
            if top == "head":
                return plan.spec_for_role("head", leaf.ndim, "head", partition)
            if top == "final_norm":
                return plan.spec_for_role("replicate", leaf.ndim, "norm", partition)
            kind = path[1].split("_", 1)[1]          # "p{j}_{kind}"
            if name in L.PARAM_ROLES["shared_expert"]:
                kind = "shared_expert"
            role = L.PARAM_ROLES[kind].get(name, "replicate")
            return plan.spec_for_role(role, leaf.ndim, kind, partition,
                                      stacked=1)

        return _tree_map_with_path(spec, shapes)

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def forward(self, params, batch: Dict[str, jax.Array],
                cache: Optional[Dict[str, Any]] = None,
                cache_pos: Optional[jax.Array] = None,
                shard_fns: Optional[Dict[str, Callable]] = None,
                embedded: Optional[jax.Array] = None,
                head_last_only: bool = False):
        """Returns (logits, new_cache). ``cache`` enables decode;
        ``embedded`` lets multi-partition drivers feed boundary activations;
        ``head_last_only`` computes logits for the final position only
        (prefill serving: (B, 1, V) instead of (B, S, V))."""
        arch = self.arch
        sf = shard_fns or {}

        def get_sf(kind):
            return sf.get(kind, lambda a, role=None: a)

        if embedded is not None:
            x = embedded
        else:
            tokens = batch["tokens"]
            x = params["embed"]["table"][tokens] if self.include_embed else None
            x = get_sf("embed")(x, role="boundary")

        B, Sq = x.shape[0], x.shape[1]
        positions = batch.get("positions")
        if positions is None:
            base = cache_pos if cache_pos is not None else 0
            positions = base + jnp.arange(Sq, dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, (B, Sq))
        mrope = batch.get("mrope_positions") if arch.mrope else None

        # ---------------- encoder (whisper) ----------------
        enc_out = None
        encoder_ran = False
        new_cache: Dict[str, Any] = {}
        for seg in self.segments:
            if not seg.encoder:
                continue
            if cache is not None and "frames" not in batch:
                # decode: encoder output (and cross KV) already cached
                enc_out = cache.get("enc_out")
                if enc_out is not None:
                    new_cache["enc_out"] = enc_out
                continue
            frames = batch["frames"]
            h = frames.astype(jnp.bfloat16)
            h = self._run_segment(params[seg.name], seg, h, None, None, None,
                                  None, None, get_sf)[0]
            enc_out = h
            encoder_ran = True
            if cache is not None:
                new_cache["enc_out"] = enc_out

        # ---------------- decoder ----------------
        for seg in self.segments:
            if seg.encoder:
                continue
            seg_cache = cache.get(seg.name) if cache is not None else None
            x, seg_new_cache = self._run_segment(
                params[seg.name], seg, x, positions, mrope,
                enc_out if (encoder_ran or cache is None) else None,
                seg_cache, cache_pos, get_sf)
            if cache is not None:
                new_cache[seg.name] = seg_new_cache

        if not self.include_head:
            return x, (new_cache if cache is not None else None)

        if head_last_only:
            x = x[:, -1:]
        x = L.apply_norm(x, params["final_norm"]["ln_scale"],
                         params["final_norm"].get("ln_bias"), arch.norm)
        w_head = (params["embed"]["table"].T if arch.tie_embeddings
                  else params["head"]["w"])
        logits = x @ w_head
        logits = get_sf("head")(logits, role="inner")
        return logits, (new_cache if cache is not None else None)

    # ------------------------------------------------------------------
    def _run_segment(self, seg_params, seg: Segment, x, positions, mrope,
                     enc_out, seg_cache, cache_pos, get_sf):
        arch = self.arch

        def body(h, slices):
            p_slice, c_slice = slices
            c_out = {}
            for j, kind in enumerate(seg.pattern):
                pk = f"p{j}_{kind}"
                p = p_slice[pk]
                c = c_slice.get(pk) if c_slice is not None else None
                sfk = get_sf(kind)
                if kind in ("attn", "enc_attn"):
                    causal = kind == "attn"
                    h, nc = A.attend(
                        h, p, num_heads=arch.num_heads,
                        num_kv_heads=arch.num_kv_heads, head_dim=arch.head_dim,
                        norm=arch.norm, causal=causal,
                        positions=positions if causal else None,
                        rope_theta=arch.rope_theta,
                        mrope_positions=mrope if causal else None,
                        cache=c, cache_pos=cache_pos,
                        attn_impl=self.attn_impl, shard_fn=sfk)
                elif kind == "mla":
                    h, nc = A.attend_mla(
                        h, p, num_heads=arch.num_heads,
                        qk_nope_head_dim=arch.qk_nope_head_dim,
                        qk_rope_head_dim=arch.qk_rope_head_dim,
                        v_head_dim=arch.v_head_dim, norm=arch.norm,
                        positions=positions, rope_theta=arch.rope_theta,
                        cache=c, cache_pos=cache_pos,
                        attn_impl=self.attn_impl, shard_fn=sfk)
                elif kind == "dsa":
                    h, nc = A.attend_dsa(
                        h, p, num_heads=arch.num_heads,
                        qk_nope_head_dim=arch.qk_nope_head_dim,
                        qk_rope_head_dim=arch.qk_rope_head_dim,
                        v_head_dim=arch.v_head_dim,
                        index_n_heads=arch.index_n_heads,
                        index_head_dim=arch.index_head_dim,
                        index_topk=arch.index_topk, norm=arch.norm,
                        positions=positions, rope_theta=arch.rope_theta,
                        cache=c, cache_pos=cache_pos, shard_fn=sfk)
                elif kind == "cross_attn":
                    h, nc = A.attend(
                        h, p, num_heads=arch.num_heads,
                        num_kv_heads=arch.num_kv_heads, head_dim=arch.head_dim,
                        norm=arch.norm, causal=False, kv_src=enc_out,
                        cache=c, write_cross=enc_out is not None,
                        attn_impl=self.attn_impl, shard_fn=sfk)
                elif kind in ("ffn", "enc_ffn"):
                    h = L.apply_ffn(h, p, arch.act if kind == "ffn" else
                                    ("gelu" if arch.act == "gelu" else arch.act),
                                    arch.norm, shard_fn=sfk)
                    nc = None
                elif kind == "moe":
                    h = M.apply_moe(h, p, top_k=arch.experts_per_token,
                                    act=arch.act, norm=arch.norm, shard_fn=sfk)
                    nc = None
                elif kind == "ssm":
                    h, nc = S.apply_ssm(h, p, d_state=arch.ssm_d_state,
                                        d_conv=arch.ssm_conv, norm=arch.norm,
                                        state=c, shard_fn=sfk)
                elif kind == "rwkv_tmix":
                    h, nc = R.apply_rwkv_tmix(h, p, head_size=arch.rwkv_head_size,
                                              norm=arch.norm, state=c,
                                              use_kernel=self.use_flash,
                                              shard_fn=sfk)
                elif kind == "rwkv_cmix":
                    h, nc = R.apply_rwkv_cmix(h, p, norm=arch.norm, state=c,
                                              shard_fn=sfk)
                else:
                    raise ValueError(kind)
                # only blocks that HAVE a cache entry emit one (ffn/moe are
                # stateless: emitting None would change the cache pytree)
                if c_slice is not None and pk in c_slice:
                    c_out[pk] = nc if nc is not None else c_slice[pk]
            return h, c_out

        scan_body = body
        if self.remat and seg_cache is None:
            scan_body = jax.checkpoint(body)

        unroll = seg.count if self.unroll else 1
        if seg_cache is None:
            def wrapped(h, p_slice):
                h, _ = scan_body(h, (p_slice, None))
                return h, None
            x, _ = jax.lax.scan(wrapped, x, seg_params, unroll=unroll)
            return x, None
        x, new_cache = jax.lax.scan(
            lambda h, s: scan_body(h, s), x, (seg_params, seg_cache),
            unroll=unroll)
        return x, new_cache

    # ------------------------------------------------------------------
    # losses / steps
    # ------------------------------------------------------------------
    def loss(self, params, batch, shard_fns=None):
        logits, _ = self.forward(params, batch, shard_fns=shard_fns)
        labels = batch["labels"]
        lf = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def init_cache(self, batch_size: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict[str, Any]:
        arch = self.arch
        cache: Dict[str, Any] = {}
        if arch.encoder_layers:
            cache["enc_out"] = jnp.zeros(
                (batch_size, arch.num_frames or 1500, arch.d_model), dtype)
        for seg in self.segments:
            if seg.encoder:
                continue
            seg_cache = {}
            for j, kind in enumerate(seg.pattern):
                pk = f"p{j}_{kind}"
                if kind == "attn":
                    kv = lambda: jnp.zeros((seg.count, batch_size, max_len,
                                            arch.num_kv_heads, arch.head_dim),
                                           dtype)
                    seg_cache[pk] = {"k": kv(), "v": kv()}
                elif kind in ("mla", "dsa"):
                    seg_cache[pk] = {
                        "c_kv": jnp.zeros((seg.count, batch_size, max_len,
                                           arch.kv_lora_rank), dtype),
                        "k_rope": jnp.zeros((seg.count, batch_size, max_len,
                                             arch.qk_rope_head_dim), dtype),
                    }
                    if kind == "dsa":
                        seg_cache[pk]["index_keys"] = jnp.zeros(
                            (seg.count, batch_size, max_len,
                             arch.index_head_dim), dtype)
                elif kind == "cross_attn":
                    F = arch.num_frames or 1500
                    kv = lambda: jnp.zeros((seg.count, batch_size, F,
                                            arch.num_kv_heads, arch.head_dim),
                                           dtype)
                    seg_cache[pk] = {"k": kv(), "v": kv()}
                elif kind == "ssm":
                    di = arch.ssm_expand * arch.d_model
                    seg_cache[pk] = {
                        "ssm": jnp.zeros((seg.count, batch_size, di,
                                          arch.ssm_d_state), jnp.float32),
                        "conv": jnp.zeros((seg.count, batch_size,
                                           arch.ssm_conv - 1, di), dtype),
                    }
                elif kind == "rwkv_tmix":
                    hs = arch.rwkv_head_size
                    H = arch.d_model // hs
                    seg_cache[pk] = {
                        "shift": jnp.zeros((seg.count, batch_size,
                                            arch.d_model), dtype),
                        "wkv": jnp.zeros((seg.count, batch_size, H, hs, hs),
                                         jnp.float32),
                    }
                elif kind == "rwkv_cmix":
                    seg_cache[pk] = {"shift": jnp.zeros(
                        (seg.count, batch_size, arch.d_model), dtype)}
            cache[seg.name] = seg_cache
        return cache

    def cache_shapes(self, batch_size: int, max_len: int):
        return jax.eval_shape(
            functools.partial(self.init_cache, batch_size, max_len))

    def cache_specs(self, plan, partition: int = 0):
        """PartitionSpec pytree mirroring init_cache."""
        from jax.sharding import PartitionSpec as P
        arch = self.arch

        def axes(t):
            if not t:
                return None
            return t[0] if len(t) == 1 else tuple(t)

        cache: Dict[str, Any] = {}
        akp = plan.kind_plan("attn", partition)
        kv_heads_ax = axes(akp.cols_axes) if (
            akp.s_out <= arch.num_kv_heads
            and arch.num_kv_heads % max(akp.s_out, 1) == 0) else None
        batch_ax = axes(akp.batch_axes)
        rows_ax = axes(akp.rows_axes)
        if arch.encoder_layers:
            ekp = plan.kind_plan("enc_attn", partition)
            cache["enc_out"] = P(axes(ekp.batch_axes), None, None)
        for seg in self.segments:
            if seg.encoder:
                continue
            seg_specs = {}
            for j, kind in enumerate(seg.pattern):
                pk = f"p{j}_{kind}"
                if kind in ("attn", "cross_attn"):
                    kv = P(None, batch_ax, rows_ax if kind == "attn" else None,
                           kv_heads_ax, None)
                    seg_specs[pk] = {"k": kv, "v": kv}
                elif kind in ("mla", "dsa"):
                    # the latent (and index-key) caches: one vector a token
                    # for all heads, never sharded over the head fold
                    mkp = plan.kind_plan(kind, partition)
                    lat = P(None, axes(mkp.batch_axes), axes(mkp.rows_axes),
                            None)
                    seg_specs[pk] = {"c_kv": lat, "k_rope": lat}
                    if kind == "dsa":
                        seg_specs[pk]["index_keys"] = lat
                elif kind == "ssm":
                    skp = plan.kind_plan("ssm", partition)
                    seg_specs[pk] = {
                        "ssm": P(None, axes(skp.batch_axes),
                                 axes(skp.cols_axes), None),
                        "conv": P(None, axes(skp.batch_axes), None,
                                  axes(skp.cols_axes)),
                    }
                elif kind == "rwkv_tmix":
                    rkp = plan.kind_plan("rwkv_tmix", partition)
                    seg_specs[pk] = {
                        "shift": P(None, axes(rkp.batch_axes), None),
                        "wkv": P(None, axes(rkp.batch_axes),
                                 axes(rkp.cols_axes), None, None),
                    }
                elif kind == "rwkv_cmix":
                    rkp = plan.kind_plan("rwkv_cmix", partition)
                    seg_specs[pk] = {"shift": P(None, axes(rkp.batch_axes),
                                                None)}
            cache[seg.name] = seg_specs
        return cache


def build_model(arch: ArchConfig, **kw) -> Model:
    return Model(arch, **kw)


# ----------------------------------------------------------------------
def _tree_map_with_path(fn, tree, path=()):
    if isinstance(tree, dict):
        return {k: _tree_map_with_path(fn, v, path + (k,))
                for k, v in tree.items()}
    return fn(path, tree)
