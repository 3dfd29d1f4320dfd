"""Fleet sweeps: one XLA executable searches MANY problems at once.

SAMO's headline tables sweep the optimiser across many model/platform
pairs, and the per-problem jax engine (search_loops.py) still compiles and
dispatches one Problem at a time. This module makes the multi-problem
sweep itself a device program:

  1. **Bucketing** — problems whose trace-shaping configuration matches
     (mode, backend rules, ModelOptions; see ``StaticSpec``, which since
     PR 3 carries no per-architecture structure, since PR 4 no platform
     identity, and since PR 5 no objective configuration) share a bucket.
     Platform resource limits, bandwidth scalars, fold-realisability
     cubes, the Eq. 5 objective selector and the Eq. 4 amortisation
     factor are ``DeviceArrays`` data, so a bucket may freely mix target
     platforms AND objectives — the paper's "many CNNs onto many
     devices" sweep is ONE bucket per trace shape, not one per
     (shape, platform, objective) cell. Within a bucket every
     per-problem constant is padded to a common shape — node count,
     decision-slot count, menu radix, scan-pair count, fold-cube size —
     with *neutral* values that provably cannot change any result
     (lowering.py documents the padding contract; tests assert padded ==
     unpadded bitwise).

  2. **Stacking** — the padded ``DeviceArrays`` (platform scalar rows
     included) and, for SA, the move tables and chain states are stacked
     along a new leading problem axis: one device-resident constant set
     for the whole bucket.

  3. **vmap** — the *same* traced chunk/sweep bodies the per-problem
     engine jits (``_bf_chunk_core``, ``_sa_scan``) are ``jax.vmap``-ed
     over the problem axis and jitted once per bucket. Because the bodies
     are shared verbatim, every random draw is chain-shaped (never
     node/edge-shaped), and padding is bitwise-neutral, the fleet returns
     per-problem optima, objectives and improvement histories IDENTICAL to
     looping the per-problem jax engine — while dispatching one XLA
     program per chunk for the whole portfolio instead of one per problem
     (and compiling once per bucket instead of once per architecture).

Entry points mirror the single-problem optimisers and return one
``OptimResult`` per problem, in input order:

    fleet_brute_force(problems, include_cuts=..., batch_size=...)
    fleet_annealing(problems, seed=..., chains=..., max_iters=...)
    fleet_rule_based(problems, multi_start=...)

``core.pipeline.optimise_portfolio`` wraps these behind the engine
registry (falling back to a per-problem host loop when jax is absent).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.accel.eval_jax import JaxEvaluator
from repro.core.accel.lowering import StaticSpec, any_dsa
from repro.core.accel.search_loops import (
    TRACE_COUNTS,
    DeviceRuleBased,
    DeviceSA,
    _construction_tables,
    _pow2ceil,
    _rb_descend_core,
    _sa_scan,
    absorb_improvements,
    build_sa_tables,
    chunk_descriptor,
)
from repro.core.hdgraph import Variables
from repro.core.optimizers.common import (
    OptimResult,
    incumbent_better,
    repair,
)
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

__all__ = ["fleet_brute_force", "fleet_annealing", "fleet_rule_based",
           "bucket_indices", "bucket_key"]


def _stack(trees):
    """Stack a list of identically-shaped pytrees along a new axis 0."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _fleet_mesh(devices: Optional[int]):
    """Resolve the ``devices`` kwarg shared by the fleet entry points:
    ``None`` keeps the single-program jits; an int D builds the 1-D
    ``dev`` mesh (``runtime_config.device_mesh``) the ``_*_shard`` twins
    map over. Returns ``(mesh_or_None, D)``."""
    if devices is None:
        return None, 1
    from repro import runtime_config
    mesh = runtime_config.device_mesh(devices)
    return mesh, int(mesh.devices.size)


def _pad_lanes(P: int, D: int) -> int:
    """Bucket lane count padded up so the ``dev`` axis divides it: ragged
    device counts ride on no-op lanes (``take=0`` for brute force,
    ``cap=0`` for rule-based, a duplicated lane otherwise — all discarded
    on the host side), the same inert-lane contract the fleets already
    use for members that run out of work."""
    return -(-P // D) * D


#: node counts round up to the next multiple of this before bucketing, so
#: nearly-equal graphs share one executable while a 35-node outlier never
#: forces 2-3x padding waste onto an 11-node majority
NODE_TIER = 4


def _node_tier(n: int) -> int:
    return -(-n // NODE_TIER) * NODE_TIER


def _platform_pads(problems) -> Tuple[int, int]:
    """(pad_vals, pad_lut) covering every member platform's fold menu, so
    a heterogeneous bucket's realisability cubes and value luts stack
    (lowering.py pads them bit-neutrally: False / -1 fill)."""
    menus = [p.platform.fold_values() for p in problems]
    return (max(len(m) for m in menus),
            max(m[-1] for m in menus) + 2)


def _bucket_key(problem, tiered: bool) -> tuple:
    """Problems with equal keys share one StaticSpec (padded node count
    included via the size tier when ``tiered``) and hence one fleet
    executable.

    The key holds ONLY trace-shaping structure: mode/exec-model, backend
    rule flags, ModelOptions, and the node-size tier. Platform identity is
    deliberately absent — resource limits, bandwidths and the fold cube
    are ``DeviceArrays`` data, so problems targeting different platforms
    stack into one bucket (heterogeneous-platform fleets). The objective
    and ``batch_amortisation`` are likewise absent since PR 5 (they are
    ``DeviceArrays.obj_latency`` / ``.batch_amortisation``): a bucket may
    mix latency- and throughput-objective problems and still share one
    executable.
    """
    b = problem.backend
    return (problem.graph.mode, problem.exec_model, b.name, b.strict_kv,
            b.intra_matching, b.inter_matching, b.scan_tying,
            tuple(sorted(b.granularity.items())), b.fixed_unity,
            dataclasses.astuple(problem.opts),
            bool(problem.graph.cut_edges),
            _node_tier(len(problem.graph.nodes)) if tiered else 0)


def bucket_key(problem, tiered: bool = False) -> tuple:
    """Public trace-signature key: problems with equal keys share one
    ``StaticSpec`` and hence one fleet executable (``_bucket_key``
    documents exactly what the key holds and why platform/objective are
    absent). ``tiered=False`` matches the rule-based/SA fleets, which is
    also what the service admission queue (``repro/service/queue.py``)
    buckets incoming requests by: requests with equal untiered keys can
    join the same in-flight lockstep round as late-joiner lanes."""
    return _bucket_key(problem, tiered)


def bucket_indices(problems, tiered: bool = True) -> List[List[int]]:
    """Group problem indices into fleet buckets (stable order).

    ``tiered`` splits buckets by node-count tier. Brute force is
    compute-bound over [B, n] chunks, so padding an 11-node graph to a
    35-node outlier costs real throughput — it buckets tiered. The SA
    sweep's arrays are chain-sized (tiny); its cost is the op count of the
    scan body, so ONE executable for the whole portfolio beats several
    tier compiles — it buckets untiered.

    Worked example — a Table-IV-style portfolio of six problems::

        idx  graph          nodes  backend   platform       mode
        0    tinyllama      11     spmd      mesh-4x4       train
        1    llama3.2       11     spmd      abstract-16    train
        2    stablelm       12     spmd      mesh-4x4       train
        3    tinyllama      11     megatron  mesh-4x4       train
        4    jamba          35     spmd      mesh-4x4       train
        5    tinyllama      11     spmd      mesh-2x8       decode

    With ``tiered=True`` (brute force, NODE_TIER=4) the buckets are
    ``[[0, 1, 2], [3], [4], [5]]``:

    * 0, 1 and 2 share backend rules, mode and node tier (11 rounds up
      to 12) — their three *platforms'* differing limit scalars and fold
      cubes are stacked data, not separate executables;
    * 3 splits on backend rule flags (megatron vs spmd shapes the trace:
      different matching/tying branches);
    * 4 splits on node tier (36 vs 12 — padding everyone to 35 nodes
      would tax the whole bucket's chunk throughput);
    * 5 splits on mode (decode changes the traced row arithmetic).

    With ``tiered=False`` (SA) the node tier is dropped, so 4 joins
    ``[0, 1, 2, 4]`` — the sweep pads its node axis bit-neutrally and the
    chain-shaped arrays don't care about graph size.
    """
    byk = {}
    for i, p in enumerate(problems):
        byk.setdefault(_bucket_key(p, tiered), []).append(i)
    return list(byk.values())


# ----------------------------------------------------------------------
# vmapped entry points (jitted once per bucket)
# ----------------------------------------------------------------------

def _fleet_bf_chunk_core(static: StaticSpec, B: int, no_cut: bool,
                         A, desc, sigma, T, cb_row, take):
    """One enumeration chunk for EVERY problem in a bucket.

    The digit decode runs with the problem axis flattened into the gather
    index space (global row offsets) instead of vmapped: XLA CPU lowers
    batched gathers to scalar loops, while flat row/element gathers stay
    vectorised — the arithmetic (and hence every decoded integer) is
    identical to ``_bf_chunk_core``. The evaluation half is the verbatim
    ``_bf_eval_part`` under ``jax.vmap``, which keeps per-problem float
    results bit-identical to the per-problem engine.

    Shared verbatim by the single-program jit (``_fleet_bf_chunk``) and
    the problem-axis-sharded one (``_fleet_bf_chunk_shard``): the body is
    per-problem independent, so running it on a P/D-lane shard computes
    exactly the rows the full program would.
    """
    from repro.core.accel.search_loops import (
        _bf_decode_digits,
        _bf_eval_part,
    )
    P, S = desc.shape[0], desc.shape[1]
    n = static.n_nodes
    mm = T.shape[-1]
    idt = A.batch.dtype
    digits = jax.vmap(functools.partial(_bf_decode_digits, B, idt))(desc)
    digits_flat = digits.transpose(0, 2, 1).reshape(P * (S + 1), B)
    offs = (jnp.arange(P, dtype=sigma.dtype) * (S + 1))[:, None, None]
    rows = (sigma + offs).reshape(-1)                   # [P*3*n] global
    dig = jnp.take(digits_flat, rows, axis=0)           # [P*3*n, B]
    T_flat = T.reshape(P * 3 * n, mm)
    val = jnp.take_along_axis(T_flat, dig, axis=1)      # [P*3*n, B]
    val = val.reshape(P, 3, n, B)
    si = val[:, 0].transpose(0, 2, 1)                   # [P, B, n]
    so = val[:, 1].transpose(0, 2, 1)
    kk = val[:, 2].transpose(0, 2, 1)
    return jax.vmap(functools.partial(_bf_eval_part, static, B, no_cut))(
        A, si, so, kk, cb_row, take)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _fleet_bf_chunk(static: StaticSpec, B: int, no_cut: bool,
                    A, desc, sigma, T, cb_row, take):
    TRACE_COUNTS["fleet_bf_chunk"] += 1
    return _fleet_bf_chunk_core(static, B, no_cut, A, desc, sigma, T,
                                cb_row, take)


def _shard_problem_axis(body, mesh, n_in: int, n_out, check_vma=True):
    """``shard_map`` a fleet bucket body over the mesh's ``dev`` axis.

    Pure data parallelism: every input and output splits its leading
    problem axis (``P("dev")`` prefix specs cover the ``DeviceArrays`` /
    SA-state pytrees leaf-wise), no collectives — each device runs the
    verbatim bucket program on its P/D-lane slice, so per-problem results
    are bit-identical to the single-program jit by construction. Callers
    pad ragged bucket sizes to a multiple of D with no-op lanes
    (``take=0`` / ``cap=0`` / duplicated lane 0, discarded on host).

    ``check_vma=False`` for bodies containing ``lax.while_loop``: their
    carries start from constants (device-invariant) and leave the body
    device-varying, which the varying-manual-axes checker rejects. The
    check only guards replicated (``P()``) outputs; every output here is
    sharded, so disabling it costs nothing.
    """
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(body, mesh=mesh, in_specs=(P("dev"),) * n_in,
                         out_specs=jax.tree_util.tree_map(
                             lambda _: P("dev"), n_out),
                         check_vma=check_vma)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _fleet_bf_chunk_shard(static: StaticSpec, B: int, no_cut: bool, mesh,
                          A, desc, sigma, T, cb_row, take):
    TRACE_COUNTS["fleet_bf_chunk_shard"] += 1
    body = functools.partial(_fleet_bf_chunk_core, static, B, no_cut)
    return _shard_problem_axis(body, mesh, 6, (0, 0, 0, 0))(
        A, desc, sigma, T, cb_row, take)


def _fleet_sa_sweeps_core(static: StaticSpec, gran, has_cut_edges: bool,
                          n_sweeps: int, A, menus, menu_sizes, clamp,
                          kv_fix, state, temps, scale, cooling, k_min):
    def one(Ai, mi, szi, ci, kfi, sti, ti, sci):
        return _sa_scan(static, gran, has_cut_edges, n_sweeps, Ai, mi,
                        szi, ci, kfi, sti, ti, sci, cooling, k_min)

    return jax.vmap(one)(A, menus, menu_sizes, clamp, kv_fix, state,
                         temps, scale)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _fleet_sa_sweeps(static: StaticSpec, gran, has_cut_edges: bool,
                     n_sweeps: int, A, menus, menu_sizes, clamp, kv_fix,
                     state, temps, scale, cooling, k_min):
    TRACE_COUNTS["fleet_sa_sweeps"] += 1
    return _fleet_sa_sweeps_core(static, gran, has_cut_edges, n_sweeps, A,
                                 menus, menu_sizes, clamp, kv_fix, state,
                                 temps, scale, cooling, k_min)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _fleet_sa_sweeps_shard(static: StaticSpec, gran, has_cut_edges: bool,
                           n_sweeps: int, mesh, A, menus, menu_sizes,
                           clamp, kv_fix, state, temps, scale, cooling,
                           k_min):
    from jax.sharding import PartitionSpec as P

    TRACE_COUNTS["fleet_sa_sweeps_shard"] += 1
    body = functools.partial(_fleet_sa_sweeps_core, static, gran,
                             has_cut_edges, n_sweeps)
    # cooling / k_min are traced schedule scalars — replicated, not
    # problem-axis data, hence the two trailing P() specs
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("dev"),) * 8 + (P(), P()),
        out_specs=(P("dev"), P("dev"), P("dev")),
    )(A, menus, menu_sizes, clamp, kv_fix, state, temps, scale,
      cooling, k_min)


# ----------------------------------------------------------------------
# brute force
# ----------------------------------------------------------------------

class _BFMember:
    """Host-side per-problem enumeration state inside one bucket."""

    def __init__(self, index: int, problem, include_cuts: bool,
                 max_cuts: int):
        from repro.core.optimizers.brute_force import _cut_sets
        self.index = index
        self.problem = problem
        self.graph = problem.graph
        self.backend = problem.backend
        self.slots, self.menus = self.backend.space(self.graph,
                                                    problem.platform)
        self.sizes = [len(m) for m in self.menus]
        self.strides = [1] * len(self.slots)
        for s in range(len(self.slots) - 2, -1, -1):
            self.strides[s] = self.strides[s + 1] * self.sizes[s + 1]
        self.total = 1
        for s in self.sizes:
            self.total *= s
        self.max_menu = max(self.sizes, default=1)
        self.n = len(self.graph.nodes)
        self.base = self.backend.initial(self.graph).with_cuts(())
        self.cut_sets = list(_cut_sets(self.graph.cut_edges, include_cuts,
                                       max_cuts))
        # search state; ``planned`` runs ahead of ``points`` by the chunks
        # still in flight (the chunk loop is software-pipelined)
        self.best_v: Optional[Variables] = None
        self.best_obj = np.inf
        self.points = 0
        self.planned = 0
        self.history: List[Tuple[int, float]] = []
        self.stopped = False

    def tables_for(self, k: int, n_pad: int, s_pad: int, mm_pad: int, idt):
        """Padded (sigma, T, cb_row) for this member's k-th cut set, or
        inert tables when the member has no k-th cut set."""
        E = max(n_pad - 1, 0)
        if k >= len(self.cut_sets):
            return (np.full((3, n_pad), s_pad, idt),
                    np.ones((3, n_pad, mm_pad), idt),
                    np.zeros(E, bool), None)
        from repro.core.optimizers.brute_force import (
            _clamp_tables,
            _slot_scopes,
        )
        cuts = self.cut_sets[k]
        scopes = _slot_scopes(self.backend, self.graph, self.slots, cuts)
        tabs = _clamp_tables(self.graph, self.slots, scopes, self.menus)
        sigma, T = _construction_tables(self.graph, self.backend,
                                        self.slots, scopes, tabs,
                                        self.menus, cuts, self.base,
                                        self.max_menu, idt)
        S = len(self.slots)
        sig = np.full((3, n_pad), s_pad, idt)
        sig[:, :self.n] = np.where(sigma == S, s_pad, sigma)
        Tp = np.ones((3, n_pad, mm_pad), idt)
        Tp[:, :self.n, :self.max_menu] = T
        cb_row = np.zeros(E, bool)
        for c in cuts:
            cb_row[c] = True
        return sig, Tp, cb_row, cuts

    def descriptor(self, produced: int, take: int, s_pad: int, idt):
        """Chunk descriptor rows (shared helper; padded slots -> digit 0)."""
        return chunk_descriptor(self.strides, self.sizes, produced, take,
                                s_pad, idt)

    def absorb(self, objs: np.ndarray, bi_si, bi_so, bi_kk,
               cb_row: np.ndarray, take: int) -> None:
        """Identical improvement bookkeeping to the per-problem engine
        (same shared helper)."""
        objs = np.asarray(objs[:take], np.float64)
        self.problem.note_batch_evals(take)
        last_imp, self.best_obj = absorb_improvements(
            objs, self.best_obj, self.points, self.history)
        if last_imp is not None:
            n = self.n
            self.best_v = Variables(
                tuple(int(e) for e in np.nonzero(cb_row[:max(n - 1, 0)])[0]),
                tuple(int(x) for x in np.asarray(bi_si)[:n]),
                tuple(int(x) for x in np.asarray(bi_so)[:n]),
                tuple(int(x) for x in np.asarray(bi_kk)[:n]))
        self.points += take

    def result(self, elapsed: float) -> OptimResult:
        best_v = self.best_v
        if best_v is None:                     # no feasible point found
            best_v = self.backend.initial(self.graph)
        best_eval = self.problem.evaluate(best_v)
        return OptimResult(best_v, best_eval, self.points, elapsed,
                           self.history, name="brute_force")


def fleet_brute_force(problems: Sequence, include_cuts: bool = False,
                      max_cuts: int = 1, max_points: Optional[int] = None,
                      batch_size: int = 4096,
                      devices: Optional[int] = None) -> List[OptimResult]:
    """Vmapped multi-problem brute force.

    Per-problem results (optimum design, objective, point count and
    improvement history) are identical to calling
    ``brute_force(problem, engine="jax", ...)`` in a loop; ``max_points``
    applies per problem. Problems are grouped into buckets (one XLA
    executable each) and each bucket's chunks run lock-step across its
    members; each result's ``seconds`` is therefore its BUCKET's wall
    time (members search simultaneously — per-problem times don't sum).

    ``devices=D`` distributes each bucket's problem lanes over the first
    D visible devices (``shard_map`` over ``runtime_config.device_mesh``;
    ragged lane counts pad with ``take=0`` no-op lanes). Results stay
    bit-identical to ``devices=None`` for any D.
    """
    mesh, D = _fleet_mesh(devices)
    results: List[Optional[OptimResult]] = [None] * len(problems)
    with _trace.span("fleet.bucketing", problems=len(problems),
                     optimiser="brute_force") as bsp:
        buckets = bucket_indices(problems)
        bsp.set(buckets=len(buckets))
    for bi, idxs in enumerate(buckets):
        # the bucket span is the members' shared wall clock (see the
        # ``seconds`` note in the docstring) — recorded when tracing is
        # on, but always timing
        bucket_sp = _trace.span("fleet.bf.bucket", bucket=bi,
                                members=len(idxs))
        bucket_sp.__enter__()
        members = [_BFMember(i, problems[i], include_cuts, max_cuts)
                   for i in idxs]
        n_pad = max(m.n for m in members)
        s_pad = max(len(m.slots) for m in members)
        mm_pad = max(m.max_menu for m in members)
        pairs_pad = max(
            (len(m.problem.batched().scan_pairs) for m in members),
            default=0) or 1
        vals_pad, lut_pad = _platform_pads(m.problem for m in members)
        jevs = [JaxEvaluator.from_problem(m.problem, pad_nodes=n_pad,
                                          pad_pairs=pairs_pad,
                                          pad_vals=vals_pad,
                                          pad_lut=lut_pad,
                                          pad_dsa=any_dsa(
                                              m.problem for m in members))
                for m in members]
        static = jevs[0].static
        assert all(j.static == static for j in jevs), \
            "bucketed problems must share a StaticSpec"
        P = len(members)
        P_pad = _pad_lanes(P, D)
        A = _stack([j.arrays for j in jevs]
                   + [jevs[0].arrays] * (P_pad - P))
        idt = np.int64 if jevs[0].arrays.batch.dtype == jnp.int64 \
            else np.int32
        B = min(batch_size, _pow2ceil(max(m.total for m in members)))

        def absorb(entry):
            out, takes_np, cb_np_k = entry
            # blocking readback: this span, not the async chunk dispatch,
            # absorbs the device compute time
            with _trace.span("fleet.d2h.bf_chunk"):
                objs, bi_si, bi_so, bi_kk = (np.asarray(x) for x in out)
            for mi, m in enumerate(members):
                take = int(takes_np[mi])
                if take > 0:
                    m.absorb(objs[mi], bi_si[mi], bi_so[mi], bi_kk[mi],
                             cb_np_k[mi], take)

        K = max(len(m.cut_sets) for m in members)
        for k in range(K):
            tables = [m.tables_for(k, n_pad, s_pad, mm_pad, idt)
                      for m in members]
            # no-op lanes padding P up to a multiple of the device count
            # reuse the inert-tables shape (take stays 0 for them)
            tables += [(np.full((3, n_pad), s_pad, idt),
                        np.ones((3, n_pad, mm_pad), idt),
                        np.zeros(max(n_pad - 1, 0), bool), None)
                       ] * (P_pad - P)
            sigma_d = jnp.asarray(np.stack([t[0] for t in tables]))
            T_d = jnp.asarray(np.stack([t[1] for t in tables]))
            cb_np = np.stack([t[2] for t in tables])
            cb_d = jnp.asarray(cb_np)
            active = [t[3] is not None and not m.stopped
                      for m, t in zip(members, tables)]
            produced = [0] * len(members)
            # 1-deep software pipeline: dispatch chunk j+1 before blocking
            # on chunk j's results, so host bookkeeping overlaps device
            # compute. ``planned`` (not ``points``) drives the budget math
            # and matches the per-problem loop's accounting exactly.
            pending: List[tuple] = []
            while True:
                takes = np.zeros(P_pad, np.int64)
                descs = np.zeros((P_pad, s_pad, 4), idt)
                descs[:, :, 0] = 1
                descs[:, :, 2] = 1
                descs[:, :, 3] = 1
                for mi, m in enumerate(members):
                    if not active[mi] or m.stopped:
                        continue
                    take = min(B, m.total - produced[mi])
                    if max_points is not None:
                        take = min(take, max_points - m.planned)
                    if take <= 0:
                        if max_points is not None and \
                                m.planned >= max_points:
                            m.stopped = True
                        active[mi] = False
                        continue
                    takes[mi] = take
                    descs[mi] = m.descriptor(produced[mi], take, s_pad, idt)
                    m.planned += take
                    produced[mi] += take
                    if produced[mi] >= m.total:
                        active[mi] = False
                    if max_points is not None and m.planned >= max_points:
                        m.stopped = True
                if not takes.any():
                    break
                if mesh is None:
                    with _metrics.device_dispatch("fleet_bf_chunk",
                                                  bucket=bi):
                        out = _fleet_bf_chunk(
                            static, B, k == 0, A, jnp.asarray(descs),
                            sigma_d, T_d, cb_d, jnp.asarray(takes))
                else:
                    with _metrics.device_dispatch("fleet_bf_chunk_shard",
                                                  bucket=bi, devices=D):
                        out = _fleet_bf_chunk_shard(
                            static, B, k == 0, mesh, A, jnp.asarray(descs),
                            sigma_d, T_d, cb_d, jnp.asarray(takes))
                pending.append((out, takes, cb_np))
                if len(pending) > 1:
                    absorb(pending.pop(0))
            for entry in pending:       # drain at the cut-set boundary
                absorb(entry)
        bucket_sp.__exit__(None, None, None)
        elapsed = bucket_sp.elapsed_s()
        for m in members:
            results[m.index] = m.result(elapsed)
    return results


# ----------------------------------------------------------------------
# simulated annealing
# ----------------------------------------------------------------------

def _bucket_tables(members: Sequence):
    """Shared bucket stacking prep for the SA and rule-based fleets:
    common pad sizes plus each member's move tables, built once with the
    clamp value axis extended to the bucket's largest platform fold value
    (``pad_val = lut_pad - 2``, exact — see ``build_sa_tables``) and the
    menu axis padded to the bucket radix with fold 1 (padded entries are
    never drawn/probed: ``menu_sizes`` is unchanged and the rule-based
    in-menu test excludes them). Returns
    ``(n_pad, pairs_pad, vals_pad, lut_pad, tabs)``."""
    n_pad = max(len(p.graph.nodes) for p in members)
    pairs_pad = max(
        (len(p.batched().scan_pairs) for p in members),
        default=0) or 1
    vals_pad, lut_pad = _platform_pads(members)
    tabs = [build_sa_tables(p, pad_nodes=n_pad, pad_val=lut_pad - 2)
            for p in members]
    mm_pad = max(t[0].shape[-1] for t in tabs)
    tabs = [(np.pad(t[0], ((0, 0), (0, 0),
                          (0, mm_pad - t[0].shape[-1])),
                    constant_values=1),) + t[1:] for t in tabs]
    return n_pad, pairs_pad, vals_pad, lut_pad, tabs


def fleet_annealing(problems: Sequence, seed: int = 0,
                    k_start: float = 1000.0, k_min: float = 1.0,
                    cooling: float = 0.98,
                    max_iters: Optional[int] = None,
                    objective_scale: Optional[float] = None,
                    chains: int = 1,
                    devices: Optional[int] = None) -> List[OptimResult]:
    """Vmapped multi-problem device SA.

    One ``lax.scan`` sweep loop advances every problem's chains in
    lock-step — proposal, on-device repair, evaluation, Metropolis and
    incumbent tracking all stay on the accelerator for the entire
    schedule (zero host round-trips mid-sweep). Per-problem trajectories
    are bit-identical to ``simulated_annealing(problem, engine="jax")``
    with the same seed: the sweep body is shared verbatim and every
    random draw is chain-shaped, so padding cannot perturb the stream.
    As in ``fleet_brute_force``, each result's ``seconds`` is its
    bucket's wall time (members sweep simultaneously).

    ``devices=D`` shards each bucket's problem lanes over the first D
    visible devices (``shard_map``; ragged lane counts duplicate lane 0,
    discarded on the host). Per-problem trajectories stay bit-identical
    to ``devices=None`` — lanes never interact.
    """
    from repro.core.optimizers.annealing import LADDER_SPREAD, _scale_for

    chains = max(chains, 1)
    mesh, D = _fleet_mesh(devices)
    results: List[Optional[OptimResult]] = [None] * len(problems)
    with _trace.span("fleet.bucketing", problems=len(problems),
                     optimiser="annealing") as bsp:
        buckets = bucket_indices(problems, tiered=False)
        bsp.set(buckets=len(buckets))
    for bi, idxs in enumerate(buckets):
        bucket_sp = _trace.span("fleet.sa.bucket", bucket=bi,
                                members=len(idxs))
        bucket_sp.__enter__()
        members = [problems[i] for i in idxs]
        n_pad, pairs_pad, vals_pad, lut_pad, tabs = _bucket_tables(members)
        dsa = any_dsa(members)
        sas = [DeviceSA(p, pad_nodes=n_pad, pad_pairs=pairs_pad,
                        pad_vals=vals_pad, pad_lut=lut_pad, pad_dsa=dsa,
                        tables=t) for p, t in zip(members, tabs)]
        static = sas[0].static
        assert all(s.static == static and s.gran == sas[0].gran
                   and s.has_cut_edges == sas[0].has_cut_edges
                   for s in sas), \
            "bucketed problems must share a StaticSpec"

        v0s, ev0s, scales, states, temps = [], [], [], [], []
        for p, sa in zip(members, sas):
            v0 = repair(p, p.backend.initial(p.graph))
            ev0 = p.evaluate(v0)
            v0s.append(v0)
            ev0s.append(ev0)
            scales.append(_scale_for(ev0, objective_scale))
            states.append(sa.init_state(v0, ev0, chains, seed))
            temps.append(jnp.asarray([k_start * (LADDER_SPREAD ** c)
                                      for c in range(chains)]))

        if max_iters is not None:
            total_sweeps = max(1, -(-max_iters // chains))
        else:
            total_sweeps = max(1, math.ceil(math.log(k_min / k_start)
                                            / math.log(cooling)))

        # ragged-device padding: duplicate lane 0 (chain states included —
        # the duplicate consumes an identical random stream and is simply
        # never read back)
        P = len(members)
        pad = _pad_lanes(P, D) - P
        stacked = (
            _stack([s.A for s in sas] + [sas[0].A] * pad),
            jnp.stack([s.menus for s in sas] + [sas[0].menus] * pad),
            jnp.stack([s.menu_sizes for s in sas]
                      + [sas[0].menu_sizes] * pad),
            jnp.stack([s.clamp for s in sas] + [sas[0].clamp] * pad),
            jnp.stack([s.kv_fix for s in sas] + [sas[0].kv_fix] * pad),
            _stack(states + [states[0]] * pad),
            jnp.stack(temps + [temps[0]] * pad),
            jnp.asarray(np.asarray(scales + [scales[0]] * pad,
                                   np.float64)),
        )
        if mesh is None:
            with _metrics.device_dispatch("fleet_sa_sweeps", bucket=bi,
                                          sweeps=total_sweeps):
                state_st, temps_st, traces = _fleet_sa_sweeps(
                    static, sas[0].gran, sas[0].has_cut_edges,
                    total_sweeps, *stacked, cooling, k_min)
        else:
            with _metrics.device_dispatch("fleet_sa_sweeps_shard",
                                          bucket=bi, sweeps=total_sweeps,
                                          devices=D):
                state_st, temps_st, traces = _fleet_sa_sweeps_shard(
                    static, sas[0].gran, sas[0].has_cut_edges,
                    total_sweeps, mesh, *stacked, cooling, k_min)
        with _trace.span("fleet.d2h.sa_traces"):
            t_obj = np.asarray(traces[0], np.float64)  # [P, sweeps, chains]
            t_feas = np.asarray(traces[1], bool)
        bucket_sp.__exit__(None, None, None)
        elapsed = bucket_sp.elapsed_s()

        for mi, (p, sa, ev0) in enumerate(zip(members, sas, ev0s)):
            history = [(0, ev0.objective)]
            g_best, g_feas = ev0.objective, ev0.feasible
            for t in range(total_sweeps):
                row_f = t_feas[mi, t]
                if row_f.any():
                    c = int(np.argmin(np.where(row_f, t_obj[mi, t], np.inf)))
                else:
                    c = int(np.argmin(t_obj[mi, t]))
                if incumbent_better(bool(row_f[c]), float(t_obj[mi, t, c]),
                                    g_feas, g_best):
                    g_best = float(t_obj[mi, t, c])
                    g_feas = bool(row_f[c])
                    history.append(((t + 1) * chains, g_best))
            member_state = jax.tree_util.tree_map(lambda x: x[mi], state_st)
            best_v, best_obj, best_feas = None, np.inf, False
            for v, o, f in sa.best_variables(member_state):
                if best_v is None or incumbent_better(f, o, best_feas,
                                                      best_obj):
                    best_v, best_obj, best_feas = v, o, f
            best_eval = p.evaluate(best_v)
            p.note_batch_evals(total_sweeps * chains)
            results[idxs[mi]] = OptimResult(
                best_v, best_eval, total_sweeps * chains, elapsed, history,
                name=f"annealing-jax{chains}")
    return results


# ----------------------------------------------------------------------
# rule based (Algorithm 2)
# ----------------------------------------------------------------------

def _fleet_rb_descend_core(static: StaticSpec, gran, A, menus, menu_sizes,
                           clamp, si, so, kk, cb_row, part_mask, pidx,
                           amort, cap):
    """One greedy descent for EVERY problem in a bucket: the verbatim
    per-problem descent body (``_rb_descend_core``) under ``jax.vmap``.
    The vmapped ``lax.while_loop`` steps while ANY lane still has
    unblocked partition nodes; lanes whose descent converged early (and
    lanes masked out with ``cap == 0`` because their problem has no
    pending request this round) are carried through unchanged — no-ops in
    lockstep with the rest of the bucket. Under the sharded jit each
    device's while loop bounds only ITS lane slice, so a converged
    device idles instead of stepping with the stragglers."""
    fn = functools.partial(_rb_descend_core, static, gran)
    return jax.vmap(fn)(A, menus, menu_sizes, clamp, si, so, kk, cb_row,
                        part_mask, pidx, amort, cap)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _fleet_rb_descend(static: StaticSpec, gran, A, menus, menu_sizes,
                      clamp, si, so, kk, cb_row, part_mask, pidx, amort,
                      cap):
    TRACE_COUNTS["fleet_rb_descend"] += 1
    return _fleet_rb_descend_core(static, gran, A, menus, menu_sizes,
                                  clamp, si, so, kk, cb_row, part_mask,
                                  pidx, amort, cap)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _fleet_rb_descend_shard(static: StaticSpec, gran, mesh, A, menus,
                            menu_sizes, clamp, si, so, kk, cb_row,
                            part_mask, pidx, amort, cap):
    TRACE_COUNTS["fleet_rb_descend_shard"] += 1
    body = functools.partial(_fleet_rb_descend_core, static, gran)
    return _shard_problem_axis(body, mesh, 12, (0, 0, 0, 0),
                               check_vma=False)(
        A, menus, menu_sizes, clamp, si, so, kk, cb_row, part_mask, pidx,
        amort, cap)


def fleet_rule_based(problems: Sequence,
                     time_budget_s: Optional[float] = None,
                     multi_start: bool = True,
                     devices: Optional[int] = None) -> List[OptimResult]:
    """Vmapped multi-problem rule-based optimisation (Algorithm 2).

    Every problem runs the SAME host control flow as the per-problem
    engine — ``rule_based._algorithm2`` is instantiated once per problem
    as a generator — but the greedy descents the generators request are
    answered in lockstep: one vmapped ``_rb_descend`` call per round
    advances every pending problem's descent to convergence, problems
    with no pending request ride along as ``cap == 0`` no-op lanes, and
    the round loop continues until every generator has returned. Because
    the merge bookkeeping is the shared host code and the descent body is
    the verbatim per-problem program, per-problem merge sequences, final
    designs, objectives, point counts and histories are identical to
    ``rule_based(problem, engine="jax")`` loops (tests assert bitwise).
    As with the other fleets, each result's ``seconds`` is its bucket's
    wall time (members descend simultaneously), and a bucket may mix
    platforms AND objectives — both are device data.

    ``time_budget_s`` is a BUCKET-level budget: every member's clock
    measures the shared lockstep wall time, so a budgeted fleet truncates
    each problem's multi-start/merge work differently than its own
    per-problem loop would — per-problem bit-identity holds only for
    ``time_budget_s=None``. ``optimise_portfolio`` therefore routes
    budgeted rule-based portfolios through the per-problem loop.

    ``devices=D`` shards each round's descent lanes over the first D
    visible devices (``shard_map``; ragged lane counts reuse the existing
    ``cap=0`` no-op-lane contract). Merge sequences and results stay
    bit-identical to ``devices=None``.
    """
    from repro.core.optimizers.rule_based import _algorithm2

    mesh, D = _fleet_mesh(devices)
    results: List[Optional[OptimResult]] = [None] * len(problems)
    with _trace.span("fleet.bucketing", problems=len(problems),
                     optimiser="rule_based") as bsp:
        buckets = bucket_indices(problems, tiered=False)
        bsp.set(buckets=len(buckets))
    for bi, idxs in enumerate(buckets):
        # attribution only: rule-based ``seconds`` comes from each
        # member's ``_algorithm2`` clock, not from the bucket span
        bucket_sp = _trace.span("fleet.rb.bucket", bucket=bi,
                                members=len(idxs))
        bucket_sp.__enter__()
        members = [problems[i] for i in idxs]
        P = len(members)
        P_pad = _pad_lanes(P, D)
        pad = P_pad - P
        n_pad, pairs_pad, vals_pad, lut_pad, tabs = _bucket_tables(members)
        dsa = any_dsa(members)
        rbs = [DeviceRuleBased(p, pad_nodes=n_pad, pad_pairs=pairs_pad,
                               pad_vals=vals_pad, pad_lut=lut_pad,
                               pad_dsa=dsa, tables=t)
               for p, t in zip(members, tabs)]
        static = rbs[0].static
        assert all(r.static == static and r.gran == rbs[0].gran
                   for r in rbs), \
            "bucketed problems must share a StaticSpec"
        A_st = _stack([r.A for r in rbs] + [rbs[0].A] * pad)
        menus_st = jnp.stack([r.menus for r in rbs]
                             + [rbs[0].menus] * pad)
        sizes_st = jnp.stack([r.menu_sizes for r in rbs]
                             + [rbs[0].menu_sizes] * pad)
        clamp_st = jnp.stack([r.clamp for r in rbs]
                             + [rbs[0].clamp] * pad)
        amort = jnp.asarray(np.asarray([r.amort for r in rbs]
                                       + [rbs[0].amort] * pad),
                            rbs[0].A.flops.dtype)
        idt_np = np.int64 if rbs[0].A.batch.dtype == jnp.int64 else np.int32

        gens = [_algorithm2(p, time_budget_s, multi_start) for p in members]
        pending: List[Optional[tuple]] = []
        for li, g in enumerate(gens):
            try:
                pending.append(next(g))
            except StopIteration as stop:    # pragma: no cover (>= 1 part)
                results[idxs[li]] = stop.value
                pending.append(None)

        E = max(n_pad - 1, 0)
        rnd = 0
        while any(req is not None for req in pending):
            si = np.ones((P_pad, n_pad), idt_np)
            so = np.ones((P_pad, n_pad), idt_np)
            kk = np.ones((P_pad, n_pad), idt_np)
            cb = np.zeros((P_pad, E), bool)
            pm = np.zeros((P_pad, n_pad), bool)
            pidx = np.zeros(P_pad, idt_np)
            cap = np.zeros(P_pad, idt_np)    # 0 => masked no-op lane
            for li, req in enumerate(pending):
                if req is None:
                    continue
                v, part = req
                (si[li], so[li], kk[li], cb[li], pm[li], pidx[li],
                 cap[li]) = rbs[li].pack_request(v, part)
            if mesh is None:
                with _metrics.device_dispatch("fleet_rb_descend",
                                              bucket=bi, round=rnd):
                    out = _fleet_rb_descend(
                        static, rbs[0].gran, A_st, menus_st, sizes_st,
                        clamp_st, jnp.asarray(si), jnp.asarray(so),
                        jnp.asarray(kk), jnp.asarray(cb), jnp.asarray(pm),
                        jnp.asarray(pidx), amort, jnp.asarray(cap))
            else:
                with _metrics.device_dispatch("fleet_rb_descend_shard",
                                              bucket=bi, round=rnd,
                                              devices=D):
                    out = _fleet_rb_descend_shard(
                        static, rbs[0].gran, mesh, A_st, menus_st,
                        sizes_st, clamp_st, jnp.asarray(si),
                        jnp.asarray(so), jnp.asarray(kk), jnp.asarray(cb),
                        jnp.asarray(pm), jnp.asarray(pidx), amort,
                        jnp.asarray(cap))
            with _trace.span("fleet.d2h.rb_descend"):
                o_si, o_so, o_kk, pts = (np.asarray(x) for x in out)
            rnd += 1
            for li, req in enumerate(pending):
                if req is None:
                    continue
                v, part = req
                resp = rbs[li].unpack(v, o_si[li], o_so[li], o_kk[li],
                                      pts[li])
                try:
                    pending[li] = gens[li].send(resp)
                except StopIteration as stop:
                    results[idxs[li]] = stop.value
                    pending[li] = None
        bucket_sp.__exit__(None, None, None)
    return results
