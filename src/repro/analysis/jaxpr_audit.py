"""Jaxpr auditor: lower every engine entry point and walk what XLA sees.

The engines' runtime checks (``assert_max_traces``, the randomized
differential suite) only observe the paths tests execute. This front-end
instead lowers every jitted engine entry point with ``jax.make_jaxpr``
over the shared example grid (``recompile_lint.example_grid``) and walks
the resulting jaxprs — the exact programs XLA would compile — for four
invariant classes:

  jaxpr/host-callback    banned host-interaction primitives inside a
                         schedule (``pure_callback``/``io_callback``/
                         ``debug_callback``/``debug_print``): one host
                         round-trip turns "one cached device program"
                         into a ping-pong.
  jaxpr/dtype-drift      float avals whose dtype differs from the
                         lowering's float dtype. Audited under x64 the
                         lowering is float64 end to end, so any f32 aval
                         is a silent downcast that quietly relaxes the
                         1e-9 scalar==jax differential contract to 1e-5
                         (and an f64 aval under an f32 lowering is the
                         mirror leak).
  jaxpr/batched-gather   gathers carrying >= 2 batching dims with a large
                         output: XLA CPU lowers vmap-batched gathers to
                         scalar loops. The fleet decode keeps the problem
                         axis flattened into the index space for exactly
                         this reason (the PR 3 fleet-decode pitfall);
                         this rule keeps it that way. Small gathers
                         (per-node menu draws inside sweep bodies) are
                         exempt via ``GATHER_SIZE_THRESHOLD``.
  jaxpr/unbounded-while  ``while`` primitives in entry points that are
                         supposed to be bounded ``scan`` programs. Only
                         the rule-based descent legitimately runs to
                         convergence (``allow_while=True`` in the
                         registry).

Adding a new engine entry point? Register a lowering in
``build_entry_points`` (see docs/static_analysis.md) — everything the
walker needs is the ClosedJaxpr plus the two flags.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.analysis import Violation

#: primitives that are host round-trips — never legal inside a schedule
BANNED_HOST_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                     "debug_print")

#: gathers at or above this many output elements with >= 2 batching dims
#: are flagged; below it they are sweep-body menu draws and harmless
GATHER_SIZE_THRESHOLD = 2048


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One audited lowering: a thunk producing the ClosedJaxpr + flags."""

    name: str
    lower: Callable[[], object]
    allow_while: bool = False
    vmapped: bool = False


# ----------------------------------------------------------------------
# jaxpr walking
# ----------------------------------------------------------------------

def iter_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and all nested jaxprs (jit / scan /
    while / cond bodies), depth-first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _sub_jaxprs(eqn):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def as_jaxpr(val):
        if isinstance(val, ClosedJaxpr):
            return val.jaxpr
        if isinstance(val, Jaxpr):
            return val
        return None

    for val in eqn.params.values():
        j = as_jaxpr(val)
        if j is not None:
            yield j
        elif isinstance(val, (tuple, list)):
            for item in val:
                j = as_jaxpr(item)
                if j is not None:
                    yield j


def _is_float(dt) -> bool:
    try:
        return np.issubdtype(dt, np.floating)
    except TypeError:        # extended dtypes (PRNG keys) aren't numeric
        return False


def _gather_batching_dims(eqn) -> int:
    dnums = eqn.params.get("dimension_numbers")
    return len(getattr(dnums, "operand_batching_dims", ()))


def audit_jaxpr(closed, name: str, *, allow_while: bool = False,
                vmapped: bool = False,
                expect_float: Optional[np.dtype] = None
                ) -> List[Violation]:
    """Walk one lowered entry point; returns at most one Violation per
    rule (the message aggregates sites) so baseline keys stay
    ``rule::entry:<name>`` — stable under unrelated edits."""
    where = f"entry:{name}"
    hosts: List[str] = []
    drifts: Dict[str, int] = {}
    gathers: List[str] = []
    whiles = 0
    if expect_float is not None:
        # constants baked at the wrong float width are drift too: an f32
        # constant upcast into an f64 program already lost its low bits
        for cv in closed.jaxpr.constvars:
            dt = getattr(cv.aval, "dtype", None)
            if dt is not None and _is_float(dt) and dt != expect_float:
                key = f"const->{np.dtype(dt).name}"
                drifts[key] = drifts.get(key, 0) + 1
    for eqn in iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if prim in BANNED_HOST_PRIMS:
            hosts.append(prim)
        if prim == "while" and not allow_while:
            whiles += 1
        if prim == "gather" and _gather_batching_dims(eqn) >= 2:
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                if aval is not None and aval.size >= GATHER_SIZE_THRESHOLD:
                    gathers.append(f"{prim}[batching_dims="
                                   f"{_gather_batching_dims(eqn)}, "
                                   f"out={tuple(aval.shape)}]")
        if expect_float is not None:
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None and _is_float(dt) \
                        and dt != expect_float:
                    key = f"{prim}->{np.dtype(dt).name}"
                    drifts[key] = drifts.get(key, 0) + 1

    out: List[Violation] = []
    if hosts:
        out.append(Violation(
            rule="jaxpr/host-callback", where=where,
            message=(f"host round-trip primitive(s) inside the schedule: "
                     f"{', '.join(sorted(set(hosts)))} — the program must "
                     f"stay on device end to end")))
    if drifts:
        sites = ", ".join(f"{k} x{v}" for k, v in sorted(drifts.items()))
        out.append(Violation(
            rule="jaxpr/dtype-drift", where=where,
            message=(f"float avals off the lowering dtype "
                     f"{np.dtype(expect_float).name}: {sites} — drift "
                     f"across the scalar==jax differential boundary")))
    if gathers:
        out.append(Violation(
            rule="jaxpr/batched-gather", where=where,
            message=(f"large vmap-batched gather(s) — scalarises on XLA "
                     f"CPU; flatten the batch axis into the index space "
                     f"instead: {'; '.join(gathers[:3])}")))
    if whiles:
        out.append(Violation(
            rule="jaxpr/unbounded-while", where=where,
            message=(f"{whiles} while_loop(s) in an entry point expected "
                     f"to be a bounded scan program")))
    return out


# ----------------------------------------------------------------------
# entry-point registry: how to lower each engine program
# ----------------------------------------------------------------------

def _fleet_members(problems):
    """Two grid problems that share a StaticSpec (same arch + backend;
    platform/objective differ — both device data by construction)."""
    first = problems[0]
    mates = [p for p in problems[1:]
             if p.graph is first.graph and p.platform is not first.platform]
    return [first, mates[0]] if mates else [first, problems[0]]


def build_entry_points(problems: Optional[Sequence] = None
                       ) -> List[EntryPoint]:
    """The audited registry. Each ``lower`` thunk mirrors the host
    prologue of the real engine driver (brute_force_jax / DeviceSA /
    DeviceRuleBased / the fleet_* loops) so the traced argument shapes
    and dtypes are exactly what production traces."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.recompile_lint import example_grid
    from repro.core.accel.eval_jax import JaxEvaluator, evaluate_batch_jax
    from repro.core.accel.fleet import (
        _BFMember,
        _bucket_tables,
        _fleet_bf_chunk,
        _fleet_rb_descend,
        _fleet_sa_sweeps,
        _platform_pads,
        _stack,
    )
    from repro.core.accel.search_loops import (
        DeviceRuleBased,
        DeviceSA,
        _bf_chunk,
        _construction_tables,
        _pow2ceil,
        _rb_descend,
        _sa_sweeps,
        chunk_descriptor,
    )

    if problems is None:
        problems = example_grid()
    p = problems[0]
    fleet = _fleet_members(problems)

    def eval_batch():
        jev = JaxEvaluator.from_problem(p)
        n = jev.n_pad
        ones = np.ones((4, n), np.int64)
        cb = np.zeros((4, max(n - 1, 0)), bool)
        return jax.make_jaxpr(evaluate_batch_jax, static_argnums=(0,))(
            jev.static, jev.arrays, ones, ones, ones, cb), \
            jev.arrays.flops.dtype

    def bf_chunk():
        from repro.core.optimizers.brute_force import (
            _clamp_tables,
            _cut_sets,
            _slot_scopes,
        )
        graph, backend = p.graph, p.backend
        slots, menus = backend.space(graph, p.platform)
        sizes = [len(m) for m in menus]
        strides = [1] * len(slots)
        for s in range(len(slots) - 2, -1, -1):
            strides[s] = strides[s + 1] * sizes[s + 1]
        total = 1
        for s in sizes:
            total *= s
        jev = JaxEvaluator.from_problem(p)
        static, A = jev.static, jev.arrays
        idt = np.int64 if A.batch.dtype == jnp.int64 else np.int32
        B = min(64, _pow2ceil(total))
        base = backend.initial(graph).with_cuts(())
        cuts = next(iter(_cut_sets(graph.cut_edges, False, 1)))
        scopes = _slot_scopes(backend, graph, slots, cuts)
        tabs = _clamp_tables(graph, slots, scopes, menus)
        sigma, T = _construction_tables(graph, backend, slots, scopes,
                                        tabs, menus, cuts, base,
                                        max(sizes, default=1), idt)
        cb_row = np.zeros(max(len(graph.nodes) - 1, 0), bool)
        take = min(B, total)
        desc = chunk_descriptor(strides, sizes, 0, take, len(slots), idt)
        return jax.make_jaxpr(_bf_chunk, static_argnums=(0, 1, 2))(
            static, B, True, A, jnp.asarray(desc), jnp.asarray(sigma),
            jnp.asarray(T), jnp.asarray(cb_row), take), A.flops.dtype

    def sa_sweeps():
        sa = DeviceSA(p)
        v0 = p.backend.initial(p.graph)
        state = sa.init_state(v0, p.evaluate(v0), chains=2, seed=0)
        temps = jnp.asarray(np.asarray([1000.0, 1300.0], np.float64))
        return jax.make_jaxpr(_sa_sweeps, static_argnums=(0, 1, 2, 3))(
            sa.static, sa.gran, sa.has_cut_edges, 3, sa.A, sa.menus,
            sa.menu_sizes, sa.clamp, sa.kv_fix, state, temps, 1.0, 0.98,
            1.0), sa.A.flops.dtype

    def rb_descend():
        rb = DeviceRuleBased(p)
        v0 = p.backend.initial(p.graph)
        req = rb.pack_descent(v0, tuple(range(rb.n_real)))
        return jax.make_jaxpr(_rb_descend, static_argnums=(0, 1))(
            rb.static, rb.gran, rb.A, rb.menus, rb.menu_sizes, rb.clamp,
            jnp.asarray(req), rb.amort_dev), rb.A.flops.dtype

    def fleet_bf_chunk():
        members = [_BFMember(i, q, False, 1)
                   for i, q in enumerate(fleet)]
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
                                          pad_lut=lut_pad)
                for m in members]
        static = jevs[0].static
        A = _stack([j.arrays for j in jevs])
        idt = np.int64 if jevs[0].arrays.batch.dtype == jnp.int64 \
            else np.int32
        B = min(64, _pow2ceil(max(m.total for m in members)))
        tables = [m.tables_for(0, n_pad, s_pad, mm_pad, idt)
                  for m in members]
        takes = np.asarray([min(B, m.total) for m in members], np.int64)
        descs = np.stack([m.descriptor(0, int(t), s_pad, idt)
                          for m, t in zip(members, takes)])
        return jax.make_jaxpr(_fleet_bf_chunk, static_argnums=(0, 1, 2))(
            static, B, True, A, jnp.asarray(descs),
            jnp.asarray(np.stack([t[0] for t in tables])),
            jnp.asarray(np.stack([t[1] for t in tables])),
            jnp.asarray(np.stack([t[2] for t in tables])),
            jnp.asarray(takes)), jevs[0].arrays.flops.dtype

    def fleet_sa_sweeps():
        n_pad, pairs_pad, vals_pad, lut_pad, tabs = _bucket_tables(fleet)
        sas = [DeviceSA(q, pad_nodes=n_pad, pad_pairs=pairs_pad,
                        pad_vals=vals_pad, pad_lut=lut_pad, tables=t)
               for q, t in zip(fleet, tabs)]
        static = sas[0].static
        states, temps = [], []
        for q, sa in zip(fleet, sas):
            v0 = q.backend.initial(q.graph)
            states.append(sa.init_state(v0, q.evaluate(v0), 2, 0))
            temps.append(jnp.asarray(np.asarray([1000.0, 1300.0],
                                                np.float64)))
        scales = jnp.asarray(np.ones(len(fleet), np.float64))
        return jax.make_jaxpr(
            _fleet_sa_sweeps, static_argnums=(0, 1, 2, 3))(
            static, sas[0].gran, sas[0].has_cut_edges, 3,
            _stack([s.A for s in sas]),
            jnp.stack([s.menus for s in sas]),
            jnp.stack([s.menu_sizes for s in sas]),
            jnp.stack([s.clamp for s in sas]),
            jnp.stack([s.kv_fix for s in sas]),
            _stack(states), jnp.stack(temps), scales, 0.98, 1.0), \
            sas[0].A.flops.dtype

    def fleet_rb_descend():
        n_pad, pairs_pad, vals_pad, lut_pad, tabs = _bucket_tables(fleet)
        rbs = [DeviceRuleBased(q, pad_nodes=n_pad, pad_pairs=pairs_pad,
                               pad_vals=vals_pad, pad_lut=lut_pad,
                               tables=t) for q, t in zip(fleet, tabs)]
        static = rbs[0].static
        idt_np = np.int64 if rbs[0].A.batch.dtype == jnp.int64 \
            else np.int32
        P, E = len(rbs), max(n_pad - 1, 0)
        si = np.ones((P, n_pad), idt_np)
        so = np.ones((P, n_pad), idt_np)
        kk = np.ones((P, n_pad), idt_np)
        cb = np.zeros((P, E), bool)
        pm = np.zeros((P, n_pad), bool)
        pidx = np.zeros(P, idt_np)
        cap = np.zeros(P, idt_np)
        for li, (q, rb) in enumerate(zip(fleet, rbs)):
            v0 = q.backend.initial(q.graph)
            (si[li], so[li], kk[li], cb[li], pm[li], pidx[li],
             cap[li]) = rb.pack_request(v0, tuple(range(rb.n_real)))
        amort = jnp.asarray(np.asarray([r.amort for r in rbs]),
                            rbs[0].A.flops.dtype)
        return jax.make_jaxpr(_fleet_rb_descend, static_argnums=(0, 1))(
            static, rbs[0].gran, _stack([r.A for r in rbs]),
            jnp.stack([r.menus for r in rbs]),
            jnp.stack([r.menu_sizes for r in rbs]),
            jnp.stack([r.clamp for r in rbs]),
            jnp.asarray(si), jnp.asarray(so), jnp.asarray(kk),
            jnp.asarray(cb), jnp.asarray(pm), jnp.asarray(pidx), amort,
            jnp.asarray(cap)), rbs[0].A.flops.dtype

    return [
        EntryPoint("eval_batch", eval_batch),
        EntryPoint("bf_chunk", bf_chunk),
        EntryPoint("sa_sweeps", sa_sweeps),
        EntryPoint("rb_descend", rb_descend, allow_while=True),
        EntryPoint("fleet_bf_chunk", fleet_bf_chunk, vmapped=True),
        EntryPoint("fleet_sa_sweeps", fleet_sa_sweeps, vmapped=True),
        EntryPoint("fleet_rb_descend", fleet_rb_descend,
                   allow_while=True, vmapped=True),
    ]


RULES = ("jaxpr/host-callback", "jaxpr/dtype-drift",
         "jaxpr/batched-gather", "jaxpr/unbounded-while")


def run(problems: Optional[Sequence] = None,
        timings: Optional[Dict[str, float]] = None
        ) -> Dict[str, List[Violation]]:
    """Lower + audit every registered entry point. Requires jax.

    ``timings``, when given, collects per-entry lowering wall times
    (``lower:<name>``) — the dominant audit cost, surfaced in the JSON
    report next to the per-rule durations."""
    import time

    out: Dict[str, List[Violation]] = {r: [] for r in RULES}
    for ep in build_entry_points(problems):
        t0 = time.perf_counter()
        closed, fdt = ep.lower()
        if timings is not None:
            timings[f"lower:{ep.name}"] = time.perf_counter() - t0
        for v in audit_jaxpr(closed, ep.name, allow_while=ep.allow_while,
                             vmapped=ep.vmapped, expect_float=fdt):
            out[v.rule].append(v)
    return out


__all__ = ["BANNED_HOST_PRIMS", "GATHER_SIZE_THRESHOLD", "EntryPoint",
           "iter_eqns", "audit_jaxpr", "build_entry_points", "RULES",
           "run"]
