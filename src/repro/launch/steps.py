"""Step functions + shardings from a SAMO ShardingPlan.

This is the bridge between the optimiser's exported plan and executable
(jit-able, dry-run-lowerable) JAX programs:

  make_train_step   full train step (loss -> grad -> AdamW) for a partition
                    that spans the whole graph, or a weight-streaming
                    partition step (boundary-activation in, cotangent out)
                    for multi-partition plans.
  make_serve_step   prefill (writes KV/state cache) or decode (one token
                    against the cache).

Shardings: parameters from ``Model.param_specs(plan)``, activations/caches
from the plan's kind plans, optimiser state optionally ZeRO-1-sharded over
the data-parallel axes (``zero1_specs``). Inside the model, plan-derived
``shard_fns`` insert with_sharding_constraint at the folded tensors so GSPMD
lowers exactly the SAMO design rather than re-deriving its own.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.exporter import ShardingPlan
from repro.models.model import Model
from repro.optim.adamw import AdamWState, adamw_init, adamw_update


def _axes(t):
    if not t:
        return None
    return t[0] if len(t) == 1 else tuple(t)


# ----------------------------------------------------------------------
# plan -> shard_fns (with_sharding_constraint at folded tensors)
# ----------------------------------------------------------------------

def shard_fns_from_plan(plan: ShardingPlan, mesh: Mesh,
                        partition: int = 0,
                        seq_parallel: bool = False) -> Dict[str, Callable]:
    decode = plan.mode == "decode"

    def fns_for(kind: str) -> Callable:
        kp = plan.kind_plan(kind, partition)
        b, r, c = _axes(kp.batch_axes), _axes(kp.rows_axes), _axes(kp.cols_axes)
        rows = None if decode else r          # decode: 1-row activations
        # Megatron sequence parallelism: boundary activations additionally
        # shard their sequence dim over the TP (cols) axes; GSPMD inserts
        # the all-gather into / reduce-scatter out of each TP region.
        sp_rows = rows
        if seq_parallel and not decode:
            parts = tuple(x for t in (rows, c) if t is not None
                          for x in ((t,) if isinstance(t, str) else t))
            sp_rows = parts[0] if len(parts) == 1 else (parts or None)

        def fn(a, role=None):
            spec = None
            if role == "boundary" and a.ndim == 3:
                spec = P(b, sp_rows, None)
            elif role == "inner" and a.ndim == 3:
                spec = P(b, rows, c)
            elif role == "heads" and a.ndim == 4:
                spec = P(b, rows, c, None)
            elif role == "experts" and a.ndim == 3:
                spec = P(c, None, None)
            if spec is None:
                return a
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, spec))

        return fn

    kinds = ("embed", "attn", "mla", "dsa", "cross_attn", "enc_attn",
             "ffn", "enc_ffn", "shared_expert", "moe", "ssm", "rwkv_tmix",
             "rwkv_cmix", "head", "norm")
    return {k: fns_for(k) for k in kinds}


# ----------------------------------------------------------------------
# ZeRO-1: shard fp32 optimiser state over the data-parallel axes
# ----------------------------------------------------------------------

def zero1_specs(param_shapes: Any, param_specs: Any, mesh: Mesh,
                dp_axes: Tuple[str, ...] = ("data",)) -> Any:
    """Extend each param's PartitionSpec with the DP axes on the largest
    still-unsharded dim that divides evenly; leaves that cannot shard stay
    as-is (norm scales etc. — negligible bytes). Axes the spec already uses
    (a PartitionSpec may map each mesh axis once) are skipped."""
    def extend(sds, spec):
        if spec is None:
            spec = P()
        entries = list(spec) + [None] * (len(sds.shape) - len(spec))
        used = set()
        for e in entries:
            if e is None:
                continue
            used.update((e,) if isinstance(e, str) else e)
        free = tuple(a for a in dp_axes if a not in used)
        if not free:
            return P(*entries) if entries else P()
        dp = 1
        for a in free:
            dp *= mesh.shape[a]
        dp_entry = free[0] if len(free) == 1 else free
        cands = [(d, sds.shape[d]) for d in range(len(sds.shape))
                 if entries[d] is None and sds.shape[d] % dp == 0
                 and sds.shape[d] >= dp]
        if not cands:
            return P(*entries) if entries else P()
        d = max(cands, key=lambda x: x[1])[0]
        entries[d] = dp_entry
        return P(*entries)

    return jax.tree.map(extend, param_shapes, param_specs,
                        is_leaf=lambda x: x is None or isinstance(x, P))


def opt_state_specs(param_shapes: Any, param_specs: Any, mesh: Mesh,
                    zero1: bool, dp_axes: Tuple[str, ...] = ("data",)):
    inner = (zero1_specs(param_shapes, param_specs, mesh, dp_axes)
             if zero1 else param_specs)
    return AdamWState(step=P(), master=inner,
                      m=jax.tree.map(lambda s: s, inner,
                                     is_leaf=lambda x: x is None
                                     or isinstance(x, P)),
                      v=jax.tree.map(lambda s: s, inner,
                                     is_leaf=lambda x: x is None
                                     or isinstance(x, P)))


def _named(mesh: Mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s if s is not None else P()),
        tree, is_leaf=lambda x: x is None or isinstance(x, P))


# ----------------------------------------------------------------------
# train steps
# ----------------------------------------------------------------------

def make_train_step(model: Model, plan: ShardingPlan, mesh: Mesh,
                    partition: int = 0, lr: float = 3e-4,
                    zero1: bool = False, seq_parallel: bool = False,
                    batch_keys: Tuple[str, ...] = ("tokens", "labels"),
                    dp_axes: Tuple[str, ...] = ("data",)):
    """Full-graph train step: (params, opt_state, batch) ->
    (params, opt_state, metrics). Returns (fn, in_shardings, out_shardings).
    """
    sf = shard_fns_from_plan(plan, mesh, partition, seq_parallel)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch, shard_fns=sf))(params)
        new_params, new_state = adamw_update(params, grads, opt_state, lr=lr)
        return new_params, new_state, {"loss": loss}

    pspecs = model.param_specs(plan, partition)
    pshapes = model.param_shapes()
    ospecs = opt_state_specs(pshapes, pspecs, mesh, zero1, dp_axes)
    bspecs = _batch_specs(plan, partition, batch_keys)
    in_sh = (_named(mesh, pspecs), _named(mesh, ospecs), _named(mesh, bspecs))
    out_sh = (_named(mesh, pspecs), _named(mesh, ospecs),
              {"loss": NamedSharding(mesh, P())})
    return step, in_sh, out_sh


def make_partition_train_step(model: Model, plan: ShardingPlan, mesh: Mesh,
                              partition: int, lr: float = 3e-4,
                              zero1: bool = False, seq_parallel: bool = False,
                              batch_keys: Tuple[str, ...] = ("tokens",),
                              dp_axes: Tuple[str, ...] = ("data",)):
    """Weight-streaming partition step (multi-partition plans, paper §III-B).

    The partition's weights are resident; boundary activations stream
    through HBM. Three flavours by position:

      first  (has embed):  (params, opt, batch, cotangent_in)
                           -> (params, opt, boundary_out)   [fwd stash]
      middle:              (params, opt, boundary_in, cotangent_in)
                           -> (params, opt, boundary_out, cotangent_out)
      last   (has head):   (params, opt, boundary_in, labels)
                           -> (params, opt, cotangent_out, loss)

    The driver runs forward over partitions 0..P-1 (stashing boundaries),
    then backward P-1..0 (streaming weights back in) — Eq. 3's |C| swaps
    appear twice for training, which t_conf accounting in the driver doubles.
    """
    sf = shard_fns_from_plan(plan, mesh, partition, seq_parallel)
    part = plan.partitions[partition]
    arch = model.arch

    def fwd(params, x_or_batch):
        if part.has_embed:
            logits_or_h, _ = model.forward(params, x_or_batch, shard_fns=sf)
        else:
            logits_or_h, _ = model.forward(
                params, {"tokens": None}, embedded=x_or_batch, shard_fns=sf)
        return logits_or_h

    if part.has_head:
        def step(params, opt_state, boundary_in, labels):
            def loss_fn(p, x):
                logits, _ = model.forward(p, {"tokens": None}, embedded=x,
                                          shard_fns=sf)
                lf = logits.astype(jnp.float32)
                logz = jax.nn.logsumexp(lf, axis=-1)
                gold = jnp.take_along_axis(
                    lf, labels[..., None], axis=-1)[..., 0]
                return jnp.mean(logz - gold)
            (loss, ), _ = (loss_fn(params, boundary_in),), None
            (loss_v, (gp, gx)) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(params, boundary_in)
            new_params, new_state = adamw_update(params, gp, opt_state, lr=lr)
            return new_params, new_state, gx, {"loss": loss_v}
    elif part.has_embed:
        def step(params, opt_state, batch, cotangent_in):
            h, vjp = jax.vjp(lambda p: fwd(p, batch), params)
            (gp,) = vjp(cotangent_in)
            new_params, new_state = adamw_update(params, gp, opt_state, lr=lr)
            return new_params, new_state, h
    else:
        def step(params, opt_state, boundary_in, cotangent_in):
            h, vjp = jax.vjp(fwd, params, boundary_in)
            gp, gx = vjp(cotangent_in)
            new_params, new_state = adamw_update(params, gp, opt_state, lr=lr)
            return new_params, new_state, h, gx

    pspecs = model.param_specs(plan, partition)
    pshapes = model.param_shapes()
    ospecs = opt_state_specs(pshapes, pspecs, mesh, zero1, dp_axes)
    act = plan.act_spec(partition)
    bspecs = _batch_specs(plan, partition, batch_keys)
    data = plan.data_spec(partition)

    if part.has_head:
        in_sh = (_named(mesh, pspecs), _named(mesh, ospecs),
                 NamedSharding(mesh, act), NamedSharding(mesh, data))
        out_sh = (_named(mesh, pspecs), _named(mesh, ospecs),
                  NamedSharding(mesh, act),
                  {"loss": NamedSharding(mesh, P())})
    elif part.has_embed:
        in_sh = (_named(mesh, pspecs), _named(mesh, ospecs),
                 _named(mesh, bspecs), NamedSharding(mesh, act))
        out_sh = (_named(mesh, pspecs), _named(mesh, ospecs),
                  NamedSharding(mesh, act))
    else:
        in_sh = (_named(mesh, pspecs), _named(mesh, ospecs),
                 NamedSharding(mesh, act), NamedSharding(mesh, act))
        out_sh = (_named(mesh, pspecs), _named(mesh, ospecs),
                  NamedSharding(mesh, act), NamedSharding(mesh, act))
    return step, in_sh, out_sh


# ----------------------------------------------------------------------
# serve steps
# ----------------------------------------------------------------------

def make_serve_step(model: Model, plan: ShardingPlan, mesh: Mesh,
                    mode: str, max_len: int, partition: int = 0,
                    batch_keys: Tuple[str, ...] = ("tokens",)):
    """prefill: (params, cache, batch) -> (logits_last, cache)
       decode:  (params, cache, batch, pos) -> (next_logits, cache)."""
    sf = shard_fns_from_plan(plan, mesh, partition)

    if mode == "prefill":
        def step(params, cache, batch):
            logits, new_cache = model.forward(
                params, batch, cache=cache, cache_pos=jnp.int32(0),
                shard_fns=sf, head_last_only=True)
            return logits, new_cache
    else:
        def step(params, cache, batch, pos):
            logits, new_cache = model.forward(
                params, batch, cache=cache, cache_pos=pos, shard_fns=sf)
            return logits, new_cache

    pspecs = model.param_specs(plan, partition)
    cspecs = model.cache_specs(plan, partition)
    bspecs = _batch_specs(plan, partition, batch_keys)
    logits_spec = _logits_spec(plan, partition)
    in_sh = [_named(mesh, pspecs), _named(mesh, cspecs), _named(mesh, bspecs)]
    if mode != "prefill":
        in_sh.append(NamedSharding(mesh, P()))
    out_sh = (NamedSharding(mesh, logits_spec), _named(mesh, cspecs))
    return step, tuple(in_sh), out_sh


def make_partition_serve_step(model: Model, plan: ShardingPlan, mesh: Mesh,
                              mode: str, max_len: int, partition: int,
                              batch_keys: Tuple[str, ...] = ("tokens",)):
    """Weight-streaming serve step for one partition of a multi-partition
    plan: boundary activations stream between partitions through HBM.

      embed partition:  (params, cache, batch[, pos]) -> (boundary, cache)
      middle partition: (params, cache, boundary[, pos]) -> (boundary, cache)
      head partition:   (params, cache, boundary[, pos]) -> (logits, cache)
    """
    sf = shard_fns_from_plan(plan, mesh, partition)
    part = plan.partitions[partition]

    def run(params, cache, x_or_batch, pos):
        last = part.has_head and mode == "prefill"
        if part.has_embed:
            out, new_cache = model.forward(params, x_or_batch, cache=cache,
                                           cache_pos=pos, shard_fns=sf,
                                           head_last_only=last)
        else:
            out, new_cache = model.forward(params, {"tokens": None},
                                           embedded=x_or_batch, cache=cache,
                                           cache_pos=pos, shard_fns=sf,
                                           head_last_only=last)
        return out, new_cache

    if mode == "prefill":
        def step(params, cache, x_or_batch):
            return run(params, cache, x_or_batch, jnp.int32(0))
    else:
        def step(params, cache, x_or_batch, pos):
            return run(params, cache, x_or_batch, pos)

    pspecs = model.param_specs(plan, partition)
    cspecs = model.cache_specs(plan, partition)
    act = plan.act_spec(partition)
    out_spec = (_logits_spec(plan, partition) if part.has_head else act)
    in3 = (_named(mesh, _batch_specs(plan, partition, batch_keys))
           if part.has_embed else NamedSharding(mesh, act))
    in_sh = [_named(mesh, pspecs), _named(mesh, cspecs), in3]
    if mode != "prefill":
        in_sh.append(NamedSharding(mesh, P()))
    out_sh = (NamedSharding(mesh, out_spec), _named(mesh, cspecs))
    return step, tuple(in_sh), out_sh


# ----------------------------------------------------------------------

def _batch_specs(plan: ShardingPlan, partition: int,
                 keys: Tuple[str, ...]):
    data = plan.data_spec(partition)
    b_ax = data[0]
    r_ax = data[1] if plan.mode != "decode" else None

    def spec(name: str):
        if name in ("tokens", "labels"):
            return P(b_ax, r_ax)
        if name == "frames":
            return P(b_ax, None, None)
        if name == "mrope_positions":
            return P(None, b_ax, r_ax)
        return P()

    return {k: spec(k) for k in keys}


def batch_shardings(plan: ShardingPlan, mesh: Mesh, batch_tree: Any,
                    partition: int = 0):
    specs = _batch_specs(plan, partition, tuple(batch_tree))
    return {k: NamedSharding(mesh, specs[k]) for k in batch_tree}


def _logits_spec(plan: ShardingPlan, partition: int):
    """(B, S, V) logits: the head kind's OWN axes (its batch/cols subsets
    are disjoint by construction; mixing kinds can duplicate a mesh axis)."""
    kp = plan.kind_plan("head", partition)
    return P(_axes(kp.batch_axes), None, _axes(kp.cols_axes))
