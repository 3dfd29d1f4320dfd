"""Multi-pod dry-run: prove every (architecture x shape x mesh) cell lowers,
compiles, fits, and report its roofline terms — without any TPU.

For each cell:
  1. SAMO optimises the mapping (rule-based, spmd backend, latency
     objective) -> ShardingPlan (possibly multi-partition for over-HBM
     models: kimi-k2 training streams weights, paper §III-B).
  2. Each UNIQUE partition signature is lowered with jax.jit(in_shardings=
     ..., out_shardings=...) against ShapeDtypeStructs and compiled.
  3. memory_analysis() proves the partition fits per-chip HBM;
     cost_analysis() gives FLOPs/bytes; collective bytes are parsed from
     the compiled HLO (all-gather / all-reduce / reduce-scatter /
     all-to-all / collective-permute operand sizes).
  4. Everything lands in a per-cell JSON under experiments/dryrun/ that
     benchmarks/roofline.py and EXPERIMENTS.md consume.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""
import argparse
import json
import os
import re
import sys
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

from repro import runtime_config

# The production meshes below need 512 host devices, and jax locks the
# device count on backend init — request it BEFORE the jax import.
# runtime_config merges the flag into any pre-existing XLA_FLAGS (the old
# inline os.environ assignment silently clobbered the caller's flags).
runtime_config.fake_devices(512)

import jax  # noqa: E402 — after fake_devices, see above
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES_BY_NAME, get_arch, shape_applicable
from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.backends import BACKENDS
from repro.core.exporter import export_plan
from repro.core.graph_builder import build_hdgraph
from repro.core.hdgraph import partitions_from_cuts
from repro.core.objectives import Problem
from repro.core.optimizers import rule_based
from repro.core.perfmodel import ModelOptions
from repro.core.platform import Platform, V5E_2POD, V5E_POD
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import input_specs
from repro.launch.steps import (
    make_partition_train_step,
    make_serve_step,
    make_train_step,
)
from repro.models.model import Model

# hardware constants (assignment brief)
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


# ----------------------------------------------------------------------
# HLO parsing: collective operand bytes
# ----------------------------------------------------------------------

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(txt: str) -> float:
    """Sum byte sizes of every tensor shape literal in `txt`."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Per-op-kind operand bytes of every collective in the HLO module.

    Convention (matches the §Roofline brief): sum of operand sizes per
    collective instruction, per device (the HLO is the per-device SPMD
    program)."""
    out = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result-shape = op-name(...operands...)
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\([^)]*\)|\S+)\s+"
                     r"([a-z\-]+)(?:-start|-done)?\(", s)
        if not m:
            continue
        op = m.group(1)
        for kind in _COLLECTIVES:
            if op == kind or op == kind + "-start":
                # operand shapes appear in the argument list after the op name
                args = s.split("(", 1)[1]
                out[kind] += _shape_bytes(args)
                break
    return out


# ----------------------------------------------------------------------
# per-cell dry-run
# ----------------------------------------------------------------------

def platform_for(mesh) -> Platform:
    axes = tuple((name, size) for name, size in
                 zip(mesh.axis_names, mesh.devices.shape))
    if len(axes) == 3:
        return Platform(name="tpu-v5e-2x256", mesh_axes=axes)
    return Platform(name="tpu-v5e-256", mesh_axes=axes)


def optimise_cell(arch: ArchConfig, shape: ShapeSpec, platform: Platform,
                  *, backend: str = "spmd", objective: str = "latency",
                  zero1: bool = True, time_budget_s: float = 60.0,
                  overrides: Optional[Dict[str, Any]] = None):
    """SAMO end-to-end for one cell -> (plan, problem, result)."""
    graph = build_hdgraph(arch, shape)
    opts = ModelOptions(zero1=zero1, **(overrides or {}))
    problem = Problem(graph=graph, platform=platform,
                      backend=BACKENDS[backend], objective=objective,
                      exec_model="spmd", opts=opts)
    result = rule_based(problem, time_budget_s=time_budget_s)
    plan = export_plan(graph, result.variables, platform, "spmd",
                       result.evaluation)
    return plan, problem, result


def _partition_signature(plan, model_arch: ArchConfig, pi: int) -> Tuple:
    part = plan.partitions[pi]
    kinds = tuple(sorted((k, kp.s_in, kp.s_out, kp.kern)
                         for k, kp in part.kinds.items()))
    n_layers = part.layer_end - part.layer_start
    pattern = tuple(model_arch.layer_kind(i) + ":" + model_arch.ffn_kind(i)
                    for i in range(part.layer_start, part.layer_end))
    return (part.has_embed, part.has_head, part.has_final_norm,
            n_layers, pattern[:4], pattern[-4:] if pattern else (),
            part.enc_end - part.enc_start, kinds)


def dryrun_cell(arch_name: str, shape_name: str, *, multi_pod: bool = False,
                zero1: bool = True, time_budget_s: float = 60.0,
                use_flash: bool = False, cost_probes: bool = True,
                overrides: Optional[Dict[str, Any]] = None,
                verbose: bool = True) -> Dict[str, Any]:
    arch = get_arch(arch_name)
    shape = SHAPES_BY_NAME[shape_name]
    if not shape_applicable(arch, shape):
        return {"arch": arch_name, "shape": shape_name, "skipped": True,
                "reason": "long_500k needs sub-quadratic attention"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    platform = platform_for(mesh)
    chips = platform.chips

    t0 = time.time()
    plan, problem, result = optimise_cell(
        arch, shape, platform, zero1=zero1, time_budget_s=time_budget_s,
        overrides=overrides)
    opt_s = time.time() - t0

    record: Dict[str, Any] = {
        "arch": arch_name, "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "chips": chips,
        "mode": shape.mode,
        "partitions": len(plan.partitions),
        "samo": {
            "optimise_seconds": round(opt_s, 2),
            "objective": result.evaluation.objective,
            "latency_s": result.evaluation.latency,
            "throughput": result.evaluation.throughput,
            "feasible": result.evaluation.feasible,
            "reconf_s": result.evaluation.reconf_time,
            "points": result.points,
        },
        "cells": [],
    }

    # analytic (SAMO model) roofline terms, aggregated over the graph
    evals = result.evaluation.node_evals
    record["samo"]["model_terms"] = {
        "compute_s": sum(e.compute_s for e in evals),
        "memory_s": sum(e.memory_s for e in evals),
        "collective_s": sum(e.collective_s for e in evals),
    }

    # lower + compile each unique partition signature
    seen: Dict[Tuple, int] = {}
    for pi, part in enumerate(plan.partitions):
        sig = _partition_signature(plan, arch, pi)
        if sig in seen:
            record["cells"].append({"partition": pi, "same_as": seen[sig]})
            continue
        seen[sig] = pi
        cell = _compile_partition(arch, shape, plan, mesh, pi,
                                  zero1=zero1, use_flash=use_flash,
                                  seq_parallel=bool((overrides or {}).get(
                                      "seq_parallel_stash")),
                                  cost_probes=cost_probes,
                                  verbose=verbose)
        cell["partition"] = pi
        record["cells"].append(cell)

    # aggregate roofline over ALL partitions (duplicates scaled in)
    agg = {"flops": 0.0, "bytes": 0.0, "collective_bytes": 0.0,
           "peak_memory_gib": 0.0}
    by_pi = {c["partition"]: c for c in record["cells"] if "same_as" not in c}
    for c in record["cells"]:
        src = by_pi[c.get("same_as", c["partition"])]
        if "error" in src:
            continue
        agg["flops"] += src["flops"]
        agg["bytes"] += src["bytes"]
        agg["collective_bytes"] += src["collective_bytes"]
        agg["peak_memory_gib"] = max(agg["peak_memory_gib"],
                                     src["peak_memory_gib"])
    record["aggregate"] = agg
    record["roofline"] = roofline_terms(agg, chips, shape, arch)
    return record


def _period(arch: ArchConfig) -> int:
    """Smallest repeating layer-pattern period."""
    p = max(arch.attn_period, 1)
    if arch.is_moe and arch.moe_period > 1:
        q = arch.moe_period
        while p % q:
            p += max(arch.attn_period, 1)
    return p


def _lower_one(arch: ArchConfig, shape: ShapeSpec, plan, mesh, pi, *,
               zero1: bool, use_flash: bool, unroll: bool,
               layer_range: Optional[Tuple[int, int]],
               include_embed: bool, include_head: bool,
               seq_parallel: bool = False):
    """Build the partition's step and (lower, compile) it. Returns compiled.

    unroll=True is the cost-probe mode: scans unrolled (exact while-body
    accounting), plain S^2 attention (no inner KV-block scan to
    under-count), and a cheap backend optimisation level — the HLO cost
    numbers are identical, codegen is ~2x faster."""
    model = Model(arch, layer_range=layer_range,
                  include_embed=include_embed, include_head=include_head,
                  attn_impl="ref" if unroll else
                  ("flash" if use_flash else "chunked"),
                  remat=shape.mode == "train", unroll=unroll)
    B = shape.global_batch
    S = shape.seq_len if shape.mode != "decode" else 1
    batch_sds = input_specs(arch, shape)
    pshapes = model.param_shapes()
    multi = len(plan.partitions) > 1

    if shape.mode == "train":
        from repro.optim.adamw import adamw_init
        oshapes = jax.eval_shape(adamw_init, pshapes)
        dp_axes = plan.dp_axes(pi) or ("data",)
        if not multi:
            step, in_sh, out_sh = make_train_step(
                model, plan, mesh, pi, zero1=zero1, seq_parallel=seq_parallel,
                batch_keys=tuple(batch_sds), dp_axes=dp_axes)
            args = (pshapes, oshapes, batch_sds)
        else:
            fwd_batch = {k: v for k, v in batch_sds.items() if k != "labels"}
            step, in_sh, out_sh = make_partition_train_step(
                model, plan, mesh, pi, zero1=zero1, seq_parallel=seq_parallel,
                batch_keys=tuple(fwd_batch), dp_axes=dp_axes)
            act = jax.ShapeDtypeStruct((B, S, arch.d_model), jnp.bfloat16)
            if include_head:
                labels = jax.ShapeDtypeStruct((B, S), jnp.int32)
                args = (pshapes, oshapes, act, labels)
            elif include_embed:
                args = (pshapes, oshapes, fwd_batch, act)
            else:
                args = (pshapes, oshapes, act, act)
    else:
        mode = "prefill" if shape.mode == "prefill" else "decode"
        # decode: one new token against a cache of seq_len entries; the
        # cache length stays seq_len (mesh-divisible) and the write slot is
        # dynamic — page-aligned cache semantics.
        max_len = shape.seq_len
        cshapes = model.cache_shapes(B, max_len)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        if not multi:
            step, in_sh, out_sh = make_serve_step(
                model, plan, mesh, mode, max_len, pi,
                batch_keys=tuple(batch_sds))
            args = (pshapes, cshapes, batch_sds) if mode == "prefill" \
                else (pshapes, cshapes, batch_sds, pos)
        else:
            from repro.launch.steps import make_partition_serve_step
            part = plan.partitions[pi]
            step, in_sh, out_sh = make_partition_serve_step(
                model, plan, mesh, mode, max_len, pi,
                batch_keys=tuple(batch_sds))
            act = jax.ShapeDtypeStruct((B, S, arch.d_model), jnp.bfloat16)
            x3 = batch_sds if part.has_embed else act
            args = (pshapes, cshapes, x3) if mode == "prefill" \
                else (pshapes, cshapes, x3, pos)

    donate = (0, 1) if shape.mode == "train" else (1,)
    with mesh:
        lowered = jax.jit(step, in_shardings=in_sh,
                          out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        if unroll or os.environ.get("DRYRUN_FAST"):
            # Backend opt level 0: identical HLO cost numbers, ~2-3x faster
            # codegen. Buffer assignment is less fused, so memory_analysis
            # is a CONSERVATIVE (upper-bound) fit check.
            return lowered.compile(
                compiler_options={"xla_backend_optimization_level": "0"})
        return lowered.compile()


def _costs_of(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(sum(coll.values())),
        "collectives": {k: v for k, v in coll.items() if v},
    }


def _compile_partition(arch: ArchConfig, shape: ShapeSpec, plan, mesh, pi,
                       *, zero1: bool, use_flash: bool,
                       seq_parallel: bool = False, cost_probes: bool = True,
                       verbose: bool) -> Dict[str, Any]:
    """Full compile (artifact + memory proof) + unrolled small-L compiles
    whose exact costs extrapolate to the partition's layer count (XLA counts
    while bodies once, so the scanned full compile under-counts)."""
    import dataclasses as _dc

    part = plan.partitions[pi]
    multi = len(plan.partitions) > 1
    n_layers = part.layer_end - part.layer_start
    out: Dict[str, Any] = {
        "layers": [part.layer_start, part.layer_end],
        "has_embed": part.has_embed, "has_head": part.has_head,
    }
    kw = dict(zero1=zero1, use_flash=use_flash, seq_parallel=seq_parallel)
    inc_e = part.has_embed or not multi
    inc_h = part.has_head or not multi
    lr_full = ((part.layer_start, part.layer_end) if multi else None)

    t0 = time.time()
    try:
        compiled = _lower_one(arch, shape, plan, mesh, pi, unroll=False,
                              layer_range=lr_full, include_embed=inc_e,
                              include_head=inc_h, **kw)
        out["compile_seconds"] = round(time.time() - t0, 2)
        mem = compiled.memory_analysis()
        peak = (getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0))
        out.update(_costs_of(compiled))
        out["peak_memory_gib"] = peak / 2**30
        out["memory"] = {
            "temp_gib": getattr(mem, "temp_size_in_bytes", 0) / 2**30,
            "arguments_gib": getattr(mem, "argument_size_in_bytes", 0) / 2**30,
            "output_gib": getattr(mem, "output_size_in_bytes", 0) / 2**30,
            "alias_gib": getattr(mem, "alias_size_in_bytes", 0) / 2**30,
        }
        del compiled
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"    p{pi}: FAILED {out['error'][:200]}", flush=True)
        return out

    if not cost_probes:
        out["cost_method"] = "scan-body-once (artifact pass only)"
        if verbose:
            print(f"    p{pi}: compiled {out['compile_seconds']}s "
                  f"peak={out['peak_memory_gib']:.2f}GiB", flush=True)
        return out

    # ---- exact-cost extrapolation via unrolled small-L compiles ----
    # probe0: lead layers only (embed/head/loss/optimiser base costs);
    # probe1: +1 period of layers. total = c0 + n_periods * (c1 - c0).
    t1 = time.time()
    try:
        period = _period(arch)
        lead = min(arch.first_k_dense, n_layers) \
            if part.layer_start == 0 else 0
        n_periods = max((n_layers - lead) // period, 0)
        if multi:
            def small(k):
                lr = (part.layer_start,
                      min(part.layer_start + lead + k * period,
                          part.layer_end))
                return _lower_one(arch, shape, plan, mesh, pi, unroll=True,
                                  layer_range=lr, include_embed=inc_e,
                                  include_head=inc_h, **kw)
        else:
            def small(k):
                enc = arch.encoder_layers
                sa = _dc.replace(arch, num_layers=lead + k * period,
                                 encoder_layers=min(enc, k) if enc else 0)
                return _lower_one(sa, shape, plan, mesh, pi, unroll=True,
                                  layer_range=None, include_embed=inc_e,
                                  include_head=inc_h, **kw)

        if n_periods <= 1:
            c = _costs_of(_lower_one(
                arch, shape, plan, mesh, pi,
                unroll=True, layer_range=lr_full, include_embed=inc_e,
                include_head=inc_h, **kw))
            scale_note = "exact-unrolled"
        else:
            c0 = _costs_of(small(0))
            c1 = _costs_of(small(1))
            c = {}
            for key in ("flops", "bytes", "collective_bytes"):
                c[key] = c0[key] + (c1[key] - c0[key]) * n_periods
            c["collectives"] = {
                k: c0["collectives"].get(k, 0.0)
                + (c1["collectives"].get(k, 0.0)
                   - c0["collectives"].get(k, 0.0)) * n_periods
                for k in set(c0["collectives"]) | set(c1["collectives"])}
            # encoder layers scale alongside decoder periods only when the
            # counts match (whisper: 12/12); note the assumption.
            scale_note = (f"extrapolated base+{period}L "
                          f"x{n_periods} periods")
        out["scanned_costs"] = {k: out[k] for k in
                                ("flops", "bytes", "collective_bytes")}
        out.update({k: c[k] for k in
                    ("flops", "bytes", "collective_bytes", "collectives")})
        out["cost_method"] = scale_note
        out["cost_seconds"] = round(time.time() - t1, 2)
    except Exception as e:  # noqa: BLE001
        out["cost_method"] = f"scan-body-once (UNDER-COUNTED): {e}"

    if verbose:
        print(f"    p{pi}: compiled {out['compile_seconds']}s "
              f"(+{out.get('cost_seconds', 0)}s costs) "
              f"peak={out['peak_memory_gib']:.2f}GiB "
              f"flops={out['flops']:.3e} coll={out['collective_bytes']:.3e}B "
              f"[{out.get('cost_method', '?')}]", flush=True)
    return out


def roofline_terms(agg: Dict[str, float], chips: int, shape: ShapeSpec,
                   arch: ArchConfig) -> Dict[str, Any]:
    """The three roofline terms (§Roofline brief). cost_analysis numbers are
    per-device (the SPMD module); collective bytes likewise."""
    compute_s = agg["flops"] / PEAK_FLOPS
    memory_s = agg["bytes"] / HBM_BW
    collective_s = agg["collective_bytes"] / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)

    # MODEL_FLOPS: 6 N D for training, 2 N D for inference (N = active params)
    n_active = arch.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode"
                                   else 1)
    mult = 6.0 if shape.mode == "train" else 2.0
    model_flops = mult * n_active * tokens
    hlo_total = agg["flops"] * chips
    return {
        **terms,
        "bottleneck": dom.replace("_s", ""),
        "model_flops": model_flops,
        "hlo_flops_total": hlo_total,
        "useful_fraction": model_flops / hlo_total if hlo_total else 0.0,
        "step_time_bound_s": max(terms.values()),
    }


# ----------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------

def all_cell_names():
    for arch in ARCHS.values():
        for sname in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            shape = SHAPES_BY_NAME[sname]
            if shape_applicable(arch, shape):
                yield arch.name, sname


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--time-budget", type=float, default=60.0)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    cells: List[Tuple[str, str]] = []
    if args.all:
        cells = list(all_cell_names())
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    # single-pod pass first (it carries the roofline table), then multi-pod
    for mp in meshes:
        for arch_name, shape_name in cells:
            tag = "2pod" if mp else "1pod"
            print(f"== {arch_name} x {shape_name} [{tag}] ==", flush=True)
            rec = dryrun_cell(arch_name, shape_name, multi_pod=mp,
                              zero1=not args.no_zero1,
                              cost_probes=not mp,   # roofline is 1-pod only
                              time_budget_s=args.time_budget)
            path = os.path.join(args.out,
                                f"{arch_name}__{shape_name}__{tag}.json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            bad = [c for c in rec.get("cells", []) if "error" in c]
            if bad:
                failures += 1
                print(f"  !! {len(bad)} partition(s) failed", flush=True)
            elif rec.get("skipped"):
                print(f"  skipped: {rec['reason']}", flush=True)
            else:
                rl = rec["roofline"]
                print(f"  ok: parts={rec['partitions']} "
                      f"bottleneck={rl['bottleneck']} "
                      f"bound={rl['step_time_bound_s']:.3f}s "
                      f"useful={rl['useful_fraction']:.2f}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
