"""On-device candidate construction: brute-force chunks, multi-chain SA,
and the rule-based greedy descent.

Enumeration throughput dies the moment candidate *construction* round-trips
to Python, so all three search loops build their candidates on device:

  brute force   a mixed-radix digit decode. The host reduces the (possibly
                > 2^63-point) global enumeration index to one small int32
                descriptor per decision slot per chunk; the device expands
                it to per-candidate digits, gathers the clamp tables,
                applies the backend's constraint propagation and evaluates
                — one fused XLA program per chunk. The enumeration order is
                IDENTICAL to the numpy/scalar engines, so the optimum and
                the improvement history match them exactly.

  annealing     a ``jax.random``-driven multi-chain sweep on ``lax.scan``:
                each sweep proposes one move per chain (cut add/remove/move
                or a joint fold-triple redraw scattered over the backend's
                tying scope), REPAIRS the proposal on device (a masked
                clamp-and-propagate step: strict-KV violations clamp to the
                largest legal menu value and re-propagate — no host
                round-trip mid-sweep), evaluates all chains in one batch,
                applies the Eq. 11 Metropolis rule per chain on a geometric
                temperature ladder, and tracks per-chain incumbents on
                device. Deterministic for a fixed seed. Unlike the host
                parallel-tempering engine there are no replica exchanges
                and fold moves always redraw the whole triple — this is a
                different (device-shaped) explorer, not a bit-identical
                port.

  rule based    Algorithm 2's greedy descent as ONE ``lax.while_loop``
                program per partition (``DeviceRuleBased`` /
                ``_rb_descend``): each step evaluates the incumbent, picks
                the slowest unblocked partition node, expands its joint
                fold menu (s_in-major — the scalar probe order) through
                the scoped scatter + single propagate pass, evaluates all
                probes WITH the incumbent in the same batch, and applies
                the feasible strictly-improving probe with the smallest
                lexicographic (collective, residency) resource delta. The
                chosen move sequence is IDENTICAL to the scalar
                reference's; Algorithm 2's outer merge loop stays on the
                host (``optimizers/rule_based._algorithm2``), shared
                verbatim by every engine.

Every random draw in the SA sweep has a shape that depends only on the
chain count — never on the (possibly padded) node or edge axis — so the
fleet engine's padded, vmapped sweep (``fleet.py``) consumes the exact
same random stream as the per-problem sweep and returns bit-identical
chains.

``propagate_jax`` is the dynamic-cut port of ``Backend.propagate``: scope
anchors are recomputed from the cut bitmask per candidate, so the same
traced program serves any partitioning; scan groups and internal-rows
anchors are array data (not trace structure), which is what lets one
executable serve every architecture in a fleet bucket.

``TRACE_COUNTS`` ticks once per *trace* of each jitted entry point — the
zero-host-round-trip tests assert a multi-sweep SA run traces exactly once
and re-runs without retracing.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.accel.eval_jax import (
    TRACE_COUNTS,
    JaxEvaluator,
    _eval_core,
)
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.core.accel.lowering import DeviceArrays, StaticSpec
from repro.core.hdgraph import Variables
from repro.core.optimizers.common import OptimResult

VARS = ("s_in", "s_out", "kern")
_DIMS = {"s_in": "rows", "s_out": "col_div", "kern": "batch"}

# TRACE_COUNTS (re-exported from eval_jax so existing callers keep working)
# is incremented inside jitted function bodies — i.e. once per TRACE, not
# per call. tests use it (via the ``assert_max_traces`` fixture) to assert
# the device loops run as single jitted programs with zero host round-trips
# and that executables are shared across problems/platforms/objectives.


def _pow2ceil(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


# ----------------------------------------------------------------------
# dynamic-cut constraint propagation (Backend.propagate on device)
# ----------------------------------------------------------------------

def propagate_jax(static: StaticSpec, A: DeviceArrays, si, so, kk, cb,
                  single_partition: bool = False):
    """Port of ``Backend.propagate`` for per-candidate cut bitmasks.

    Anchors (scan-group first member, partition first node, partition first
    non-internal node) are gathered from the pre-mutation arrays, matching
    the host's copy-then-assign order. ``single_partition`` promises cb is
    all-False at trace time, collapsing the partition ids to a constant.
    """
    n = static.n_nodes
    C = si.shape[0]
    idt = A.batch.dtype
    one = jnp.ones((), idt)
    iota = jnp.arange(n, dtype=idt)
    if not single_partition:
        pid = jnp.concatenate(
            [jnp.zeros((C, 1), idt), jnp.cumsum(cb.astype(idt), axis=1)],
            axis=1)

    if static.scan_tying:
        # harmonise scan-group folds within each partition: for member a the
        # anchor is the first member b with pid[b] == pid[a] (pid is
        # monotone and members ascend, so that b is the group's first
        # member in a's partition). Non-members anchor to themselves.
        sg = A.scan_group
        grp = (sg[:, None] == sg[None, :]) & (sg[:, None] >= 0)   # [n, n]
        if single_partition:
            ok = jnp.broadcast_to(grp[None, :, :], (C, n, n))
        else:
            ok = grp[None, :, :] & (pid[:, :, None] == pid[:, None, :])
        anchor = jnp.argmax(ok, axis=2).astype(idt)
        anchor = jnp.where(sg[None, :] >= 0, anchor,
                           jnp.broadcast_to(iota[None, :], (C, n)))
        si = jnp.take_along_axis(si, anchor, 1)
        so = jnp.take_along_axis(so, anchor, 1)
        kk = jnp.take_along_axis(kk, anchor, 1)

    if static.intra_matching:
        so = jnp.where(A.elementwise[None, :], si, so)

    if static.inter_matching:
        if single_partition:
            anchor_k = kk[:, 0][:, None]
            # partition's first non-internal node (padded columns are
            # non-internal with fold 1, so an all-internal real graph
            # anchors at fold 1 either way — the host's fallback value)
            f1 = jnp.where(A.internal, n, iota)
            ni = jnp.argmin(f1)
            anchor_si = jnp.where(
                jnp.min(f1) < n,
                jnp.take(si, ni, axis=1), one)[:, None]
        else:
            is_start = jnp.concatenate([jnp.ones((C, 1), bool), cb], axis=1)
            start_idx = jax.lax.cummax(
                jnp.where(is_start, iota[None, :], 0), axis=1)
            anchor_k = jnp.take_along_axis(kk, start_idx, 1)
            # first non-internal node of each partition (may be after j):
            # dense per-partition min of (j | internal -> n), gathered back
            f = jnp.broadcast_to(jnp.where(A.internal, n, iota)[None, :],
                                 (C, n))
            onehot = pid[:, :, None] == iota[None, None, :]
            segmin = jnp.min(jnp.where(onehot, f[:, :, None], n), axis=1)
            anchor_ni = jnp.take_along_axis(segmin, pid, 1)
            anchor_si = jnp.where(
                anchor_ni < n,
                jnp.take_along_axis(si, jnp.minimum(anchor_ni, n - 1), 1),
                one)
        kk = jnp.where(A.batch % anchor_k == 0, anchor_k, one)
        si_new = jnp.where(A.rows % anchor_si == 0, anchor_si, one)
        si = jnp.where(A.internal[None, :], si, si_new)
        if static.intra_matching:
            so = jnp.where(A.elementwise[None, :], si, so)
    return si, so, kk


def _scope_mask(g: str, same_part, scan_groups, sg_i, oh_i):
    """``Backend.scope`` as a node mask for one granularity: which nodes
    share a variable with the chosen node — the whole partition
    (``global``), the node's scan group within the partition (``group``,
    falling back to the node itself when it has no group), or the node
    alone. Shape-generic (operands [n] or broadcast [C, n]); shared by
    the scatter (``_scatter_triple``) and the rule-based unblock step so
    the two can never drift apart."""
    if g == "global":
        return same_part
    if g == "group":
        return jnp.where(sg_i >= 0, same_part & (scan_groups == sg_i),
                         oh_i)
    return oh_i


def _scatter_triple(static: StaticSpec, gran: Tuple[str, str, str],
                    A: DeviceArrays, clamp, si, so, kk, cb, i, v3):
    """``Backend.set_fold`` of a joint fold triple, batched on device.

    Scatters the (per-node clamped) values of ``v3`` [3, C] over node
    ``i``'s tying scope in each of the C rows — global granularity writes
    the whole partition, group granularity the node's scan group within
    the partition, node granularity the node itself; globally-tied s_in
    skips decode split-KV (internal-rows) nodes exactly like the host —
    then ONE ``propagate_jax`` pass restores the backend's matching and
    tying invariants. Shared by the SA proposal and the rule-based probe
    construction, whose scalar references both build candidates through
    sequential ``set_fold`` calls: for the real backends the composition
    scatter-all-then-propagate-once is equivalent (the cross-engine parity
    tests assert it across every example arch and the randomized graphs).
    """
    n = static.n_nodes
    idt = A.batch.dtype
    iota_n = jnp.arange(n, dtype=idt)
    C = si.shape[0]
    pid = jnp.concatenate(
        [jnp.zeros((C, 1), idt), jnp.cumsum(cb.astype(idt), axis=1)],
        axis=1)
    pid_i = jnp.take_along_axis(pid, i[:, None], 1)
    same_part = pid == pid_i
    sg_i = A.scan_group[i]
    oh_i = iota_n[None, :] == i[:, None]
    fold = {"s_in": si, "s_out": so, "kern": kk}
    for vi, var in enumerate(VARS):
        g = gran[vi]
        m = _scope_mask(g, same_part, A.scan_group[None, :],
                        sg_i[:, None], oh_i)
        if var == "s_in" and g == "global":
            m = m & ~A.internal[None, :]     # decode split-KV keeps s_I
        clamped = clamp[vi][iota_n[None, :], v3[vi][:, None]]
        fold[var] = jnp.where(m, clamped, fold[var])
    return propagate_jax(static, A, fold["s_in"], fold["s_out"],
                         fold["kern"], cb)


def repair_jax(static: StaticSpec, A: DeviceArrays, kv_fix, si, so, kk, cb):
    """On-device feasibility repair: one masked clamp-and-propagate step.

    Strict-KV backends can propose s_out values that a tying-scope scatter
    clamped legally for the drawn node but that exceed another node's KV
    head limit (Eq. 8 side constraint). The host engines round-trip such
    proposals through ``Problem.evaluate`` and reject; here the violating
    columns clamp to ``kv_fix`` (the node's largest menu value <= its KV
    limit, host-precomputed) and ONE ``propagate_jax`` pass restores the
    backend's tying/matching invariants — tied scopes share kind and KV
    limit, so every member of a violating scope clamps to the same value
    and the propagated design stays consistent. Entirely traced: the SA
    sweep never leaves the device to repair a move.
    """
    if not static.strict_kv:
        return si, so, kk
    kvl = A.kv_limit
    viol = (kvl[None, :] > 0) & (so > kvl[None, :])
    so = jnp.where(viol, kv_fix[None, :].astype(so.dtype), so)
    return propagate_jax(static, A, si, so, kk, cb)


# ----------------------------------------------------------------------
# brute force: mixed-radix decode + evaluate, one XLA program per chunk
# ----------------------------------------------------------------------

def _construction_tables(graph, backend, slots, scopes, tabs_py, menus,
                         cuts, base, max_menu, idt):
    """Fold the scatter + ``Backend.propagate`` composition for one fixed
    cut set into per-(var, node) value tables.

    After ``set_fold``'s scatter, propagation rewrites every node from a
    single source: scan tying copies the group's first member in the
    node's partition; inter matching reads the partition's first node
    (kern) / first non-internal node (s_in); intra copies s_in into s_out
    on elementwise nodes. Each source is one node whose scattered value is
    a function of exactly ONE slot's digit — so the final value at
    (var, j) is ``T[var][j][digit of slot sigma[var][j]]``, with a
    sentinel slot index S whose digit is always 0 for constants. The
    device construction then needs one gather per variable and no
    propagation at all.
    """
    n = len(graph.nodes)
    S = len(slots)
    base_vals = {"s_in": base.s_in, "s_out": base.s_out, "kern": base.kern}
    sigma0 = {var: np.full(n, -1, np.int64) for var in VARS}
    for s, (_, var) in enumerate(slots):
        for j in scopes[s]:
            sigma0[var][j] = s

    def value0(var, m):
        """(slot or -1, value-over-digit array) as scattered at node m."""
        s = int(sigma0[var][m])
        if s < 0:
            return -1, np.full(max_menu, base_vals[var][m], np.int64)
        tab = tabs_py[s][m]                 # clamped menu values at node m
        out = np.full(max_menu, tab[-1], np.int64)   # padding never hit
        out[:len(tab)] = tab
        return s, out

    bounds = [0] + [c + 1 for c in sorted(cuts)] + [n]
    part_start = np.zeros(n, np.int64)
    part_ni = np.full(n, -1, np.int64)      # first non-internal in partition
    anchor = np.arange(n)                   # scan-tying source node
    for b in range(len(bounds) - 1):
        first = {}
        ni = -1
        for j in range(bounds[b], bounds[b + 1]):
            if ni < 0 and not graph.nodes[j].internal_rows:
                ni = j
        for j in range(bounds[b], bounds[b + 1]):
            part_start[j] = bounds[b]
            part_ni[j] = ni
            g = graph.nodes[j].scan_group
            if backend.scan_tying and g >= 0:
                if g not in first:
                    first[g] = j
                anchor[j] = first[g]

    sigma = np.full((3, n), S, idt)
    T = np.ones((3, n, max_menu), idt)

    def assign(vi, j, src_slot, vals):
        if src_slot < 0:
            T[vi, j, :] = vals[0]           # constant: sentinel digit 0
        else:
            sigma[vi, j] = src_slot
            T[vi, j, :] = vals

    for j in range(n):
        node = graph.nodes[j]
        # ---- kern: inter anchors at the partition's first node ----------
        if backend.inter_matching:
            src = int(anchor[part_start[j]])
            s_src, vals = value0("kern", src)
            vals = np.where(node.batch % np.maximum(vals, 1) == 0, vals, 1)
        else:
            src = int(anchor[j])
            s_src, vals = value0("kern", src)
        assign(2, j, s_src, vals)
        # ---- s_in: inter anchors at the first non-internal node ---------
        if backend.inter_matching and not node.internal_rows:
            ni = int(part_ni[j])
            if ni < 0:
                s_src, vals = -1, np.ones(max_menu, np.int64)
            else:
                s_src, vals = value0("s_in", int(anchor[ni]))
            vals = np.where(node.rows % np.maximum(vals, 1) == 0, vals, 1)
        else:
            s_src, vals = value0("s_in", int(anchor[j]))
        assign(0, j, s_src, vals)
        si_slot, si_vals = (sigma[0, j], T[0, j].copy())
        # ---- s_out: intra copies the final s_in on elementwise nodes ----
        if backend.intra_matching and node.elementwise:
            sigma[1, j] = si_slot
            T[1, j, :] = si_vals
        else:
            s_src, vals = value0("s_out", int(anchor[j]))
            assign(1, j, s_src, vals)
    return sigma, T


def chunk_descriptor(strides, sizes, produced: int, take: int,
                     s_pad: int, idt) -> np.ndarray:
    """Host-side mixed-radix descriptor for one enumeration chunk.

    One row per decision slot, padded to ``s_pad`` rows (padded rows
    decode to digit 0 — see ``_bf_decode_digits``). Shared by the
    per-problem engine and the fleet so the subtle slow-slot carry term
    can never drift between them (their bit-identity depends on it).
    """
    desc = np.zeros((s_pad, 4), idt)
    desc[:, 0] = 1
    desc[:, 2] = 1
    desc[:, 3] = 1
    for s in range(len(sizes)):
        stride, size = strides[s], sizes[s]
        if stride >= take:
            # slow slot: at most one digit boundary inside the chunk
            q, r = divmod(produced, stride)
            desc[s] = (0, q % size, min(stride - r, take + 1), size)
        else:
            # fast slot: the digit is periodic with period stride*size
            # (small, since stride < take <= chunk)
            desc[s] = (1, produced % (stride * size), stride, size)
    return desc


def absorb_improvements(objs: np.ndarray, best_obj: float, points: int,
                        history: List[Tuple[int, float]]):
    """Exact scalar-engine history bookkeeping for one evaluated chunk:
    record every strict improvement over the running best, in enumeration
    order. Returns (row of the last improvement or None, new best).
    Shared by the per-problem engine and the fleet."""
    prefix = np.minimum.accumulate(
        np.concatenate(([best_obj], objs)))[:-1]
    imp = np.nonzero(objs < prefix)[0]
    for r in imp:
        history.append((points + int(r) + 1, float(objs[r])))
    if len(imp):
        return int(imp[-1]), float(objs[imp[-1]])
    return None, best_obj


def _bf_decode_digits(B: int, idt, desc, start=0):
    """Per-slot digits of a chunk, [B, S+1] (last column: the sentinel
    slot, always digit 0).

    ``desc[s] = (kind, a, b, size)``: for a slow slot (stride >= chunk) the
    digit is ``(a + (off >= b)) % size`` (one carry inside the chunk, at
    offset ``b``); for a fast slot it is ``((a + off) // b) % size``. The
    host reduced the global index modulo stride/period BEFORE building the
    descriptor, so everything here fits 32 bits even for > 2^63 spaces.

    ``start`` offsets the chunk-local rows — the sharded chunk program
    decodes rows ``[start, start + B)`` of the SAME descriptor on each
    device, so a D-way shard reproduces the single-device digits exactly.
    """
    off = start + jnp.arange(B, dtype=idt)
    kind, a, b, size = desc[:, 0], desc[:, 1], desc[:, 2], desc[:, 3]
    digit_slow = (a[None, :]
                  + (off[:, None] >= b[None, :]).astype(idt)) % size[None, :]
    digit_fast = ((a[None, :] + off[:, None])
                  // jnp.maximum(b[None, :], 1)) % size[None, :]
    digits = jnp.where(kind[None, :] == 1, digit_fast,
                       digit_slow)                             # [B, S]
    return jnp.concatenate(
        [digits, jnp.zeros((B, 1), idt)], axis=1)              # sentinel


def _bf_eval_part(static: StaticSpec, B: int, no_cut: bool,
                  A: DeviceArrays, si, so, kk, cb_row, take, start=0):
    """Evaluate one decoded chunk; shared VERBATIM by the per-problem jit
    and the fleet vmap, which (with the decode being exact integer
    arithmetic) makes their per-problem results bit-identical. ``start``
    shifts the rows' global-within-chunk offsets (sharded chunks), so the
    ``off < take`` feasibility mask stays chunk-global."""
    n = static.n_nodes
    idt = A.batch.dtype
    off = start + jnp.arange(B, dtype=idt)
    cb = jnp.broadcast_to(cb_row[None, :], (B, max(n - 1, 0)))
    res = _eval_core(static, A, si, so, kk, cb, single_partition=no_cut)
    objs = jnp.where(res["feasible"] & (off < take), res["objective"],
                     jnp.inf)
    r = jnp.argmin(objs)
    return objs, si[r], so[r], kk[r]


def _bf_chunk_core(static: StaticSpec, B: int, no_cut: bool,
                   A: DeviceArrays, desc, sigma, T, cb_row, take):
    """Decode + evaluate one enumeration chunk of B candidates on device.

    Construction is three gathers through the precomputed propagation
    tables (see ``_construction_tables``); no on-device propagation. The
    fleet engine uses the same digit/value arithmetic with the problem
    axis flattened into the gather index space (batched gathers scalarise
    on CPU; flat gathers do not) — see ``fleet._fleet_bf_chunk``.
    """
    n = static.n_nodes
    idt = A.batch.dtype
    digits = _bf_decode_digits(B, idt, desc).T                 # [S+1, B]
    iota_n = jnp.arange(n, dtype=idt)
    si = T[0][iota_n[:, None], digits[sigma[0]]].T             # [B, n]
    so = T[1][iota_n[:, None], digits[sigma[1]]].T
    kk = T[2][iota_n[:, None], digits[sigma[2]]].T
    return _bf_eval_part(static, B, no_cut, A, si, so, kk, cb_row, take)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _bf_chunk(static: StaticSpec, B: int, no_cut: bool,
              A: DeviceArrays, desc, sigma, T, cb_row, take):
    TRACE_COUNTS["bf_chunk"] += 1
    return _bf_chunk_core(static, B, no_cut, A, desc, sigma, T, cb_row, take)


def _bf_shard_chunk(static: StaticSpec, B: int, no_cut: bool, D: int,
                    A: DeviceArrays, desc, sigma, T, cb_row, take):
    """Per-device body of the sharded chunk program (docs/distributed.md).

    Device ``d`` of ``D`` decodes and evaluates the disjoint mixed-radix
    range ``[d*B/D, (d+1)*B/D)`` of the chunk — same descriptor, shifted
    ``start`` — so the union of the device-local rows is bit-identical to
    the single-device ``_bf_chunk_core`` output. The incumbent combine is
    an argmin over the device axis done with statically-replicated
    collectives only (``pmin`` + masked ``psum``): device order equals
    enumeration order and ``jnp.argmin`` is first-occurrence, so the
    winning device's local argmin IS the chunk's first-occurrence global
    argmin (all-infeasible chunks degrade to device 0's row 0, exactly
    like ``argmin`` over an all-inf vector).
    """
    n = static.n_nodes
    idt = A.batch.dtype
    d = jax.lax.axis_index("dev").astype(idt)
    Bl = B // D
    start = d * Bl
    digits = _bf_decode_digits(Bl, idt, desc, start=start).T   # [S+1, Bl]
    iota_n = jnp.arange(n, dtype=idt)
    si = T[0][iota_n[:, None], digits[sigma[0]]].T             # [Bl, n]
    so = T[1][iota_n[:, None], digits[sigma[1]]].T
    kk = T[2][iota_n[:, None], digits[sigma[2]]].T
    objs, bsi, bso, bkk = _bf_eval_part(static, Bl, no_cut, A, si, so, kk,
                                        cb_row, take, start=start)
    local = jnp.min(objs)
    gmin = jax.lax.pmin(local, "dev")
    winner = jax.lax.pmin(
        jnp.where(local == gmin, d, jnp.asarray(D, idt)), "dev")
    pick = d == winner
    bsi = jax.lax.psum(jnp.where(pick, bsi, 0), "dev")
    bso = jax.lax.psum(jnp.where(pick, bso, 0), "dev")
    bkk = jax.lax.psum(jnp.where(pick, bkk, 0), "dev")
    return objs, bsi, bso, bkk


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _bf_chunk_shard(static: StaticSpec, B: int, no_cut: bool, mesh,
                    A: DeviceArrays, desc, sigma, T, cb_row, take):
    """D-way sharded twin of ``_bf_chunk``: inputs replicated, the chunk's
    row axis split over the mesh's ``dev`` axis, objs reassembled in
    enumeration order by the ``P("dev")`` out-spec. ``mesh`` is hashable,
    so it rides along as one more static argument and device counts get
    their own executables (asserted via the ``bf_chunk_shard`` trace key).
    """
    from jax.sharding import PartitionSpec as P

    TRACE_COUNTS["bf_chunk_shard"] += 1
    D = int(mesh.devices.size)
    body = functools.partial(_bf_shard_chunk, static, B, no_cut, D)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P()),
        out_specs=(P("dev"), P(), P(), P()),
    )(A, desc, sigma, T, cb_row, take)


def brute_force_jax(problem, include_cuts: bool, max_cuts: int,
                    max_points: Optional[int], time_budget_s: Optional[float],
                    batch_size: int,
                    devices: Optional[int] = None) -> OptimResult:
    """The jax engine behind ``optimizers.brute_force(engine="jax")``.

    Same enumeration order (hence identical optimum and history) as the
    numpy engine; candidate construction and evaluation run on device. Each
    cut set is enumerated in fixed-size padded chunks so the XLA program
    compiles once per problem family.

    ``devices=D`` shards each chunk's row axis over the first D visible
    devices (``runtime_config.device_mesh``), ``batch_size`` rows per
    device; results stay bit-identical to ``devices=None`` — the
    single-device program — for any D (the randomized differential suite
    asserts the {1, 2, 8} grid; the history is chunking-invariant).
    """
    from repro.core.optimizers.brute_force import (
        _clamp_tables,
        _cut_sets,
        _slot_scopes,
    )

    graph, backend = problem.graph, problem.backend
    slots, menus = backend.space(graph, problem.platform)
    sizes = [len(m) for m in menus]
    strides = [1] * len(slots)                    # itertools.product order:
    for s in range(len(slots) - 2, -1, -1):       # last slot varies fastest
        strides[s] = strides[s + 1] * sizes[s + 1]
    total = 1
    for s in sizes:
        total *= s
    max_menu = max(sizes, default=1)
    n = len(graph.nodes)

    jev = JaxEvaluator.from_problem(problem)
    static, A = jev.static, jev.arrays
    idt = np.int64 if A.batch.dtype == jnp.int64 else np.int32
    B = min(batch_size, _pow2ceil(total))
    mesh = None
    if devices is not None:
        from repro import runtime_config
        mesh = runtime_config.device_mesh(devices)
        D = int(mesh.devices.size)
        # each device takes the single-device chunk's B rows: the TPU
        # compiler's float32 rounding of a row's objective depends on the
        # compiled row count, so a B/D-row slice would drift by an ulp
        B *= D

    base = backend.initial(graph).with_cuts(())

    best_v: Optional[Variables] = None
    best_obj = np.inf
    points = 0
    history: List[Tuple[int, float]] = []
    stop = False

    # the span is the engine's wall clock (enabled or not) — the same
    # perf_counter pair the scalar/numpy engines use, so OptimResult
    # timing attribution is engine-independent
    with _trace.span("optim.brute_force.jax", total=total,
                     batch=B) as run_sp:
        for cuts in _cut_sets(graph.cut_edges, include_cuts, max_cuts):
            if stop:
                break
            scopes = _slot_scopes(backend, graph, slots, cuts)
            tabs_py = _clamp_tables(graph, slots, scopes, menus)
            sigma, T = _construction_tables(graph, backend, slots, scopes,
                                            tabs_py, menus, cuts, base,
                                            max_menu, idt)
            sigma_d = jnp.asarray(sigma)
            T_d = jnp.asarray(T)
            cb_row = np.zeros(max(n - 1, 0), bool)
            for c in cuts:
                cb_row[c] = True
            cb_row_d = jnp.asarray(cb_row)

            produced = 0
            while produced < total:
                take = min(B, total - produced)
                if max_points is not None:
                    take = min(take, max_points - points)
                if take <= 0:
                    stop = True
                    break
                desc = chunk_descriptor(strides, sizes, produced, take,
                                        len(slots), idt)
                if mesh is None:
                    with _metrics.device_dispatch("bf_chunk", take=take):
                        objs, bi_si, bi_so, bi_kk = _bf_chunk(
                            static, B, not cuts, A, jnp.asarray(desc),
                            sigma_d, T_d, cb_row_d, take)
                else:
                    with _metrics.device_dispatch("bf_chunk_shard",
                                                  take=take, devices=D):
                        objs, bi_si, bi_so, bi_kk = _bf_chunk_shard(
                            static, B, not cuts, mesh, A, jnp.asarray(desc),
                            sigma_d, T_d, cb_row_d, take)
                # blocking readback: this span, not the async dispatch
                # above, absorbs the device compute time
                with _trace.span("accel.d2h.bf_chunk", take=take):
                    objs = np.asarray(objs[:take], np.float64)
                problem.note_batch_evals(take)
                last_imp, best_obj = absorb_improvements(objs, best_obj,
                                                         points, history)
                if last_imp is not None:
                    best_v = Variables(
                        tuple(int(e) for e in np.nonzero(cb_row)[0]),
                        tuple(int(x) for x in np.asarray(bi_si)),
                        tuple(int(x) for x in np.asarray(bi_so)),
                        tuple(int(x) for x in np.asarray(bi_kk)))
                points += take
                produced += take
                if max_points is not None and points >= max_points:
                    stop = True
                    break
                if time_budget_s is not None and \
                        run_sp.elapsed_s() > time_budget_s:
                    stop = True
                    break

    elapsed = run_sp.elapsed_s()
    if best_v is None:                         # no feasible point found
        best_v = backend.initial(graph)
    best_eval = problem.evaluate(best_v)
    return OptimResult(best_v, best_eval, points, elapsed, history,
                       name="brute_force")


# ----------------------------------------------------------------------
# multi-chain simulated annealing, one lax.scan sweep loop on device
# ----------------------------------------------------------------------

@_trace.traced("accel.build_sa_tables")
def build_sa_tables(problem, *, pad_nodes: Optional[int] = None,
                    pad_menu: Optional[int] = None,
                    pad_val: Optional[int] = None):
    """Host-precomputed move tables for the device SA sweep.

    Returns numpy arrays (menus [3, n, mm], menu_sizes [3, n], clamp
    [3, n, max_val+1], kv_fix [n]) plus the backend's granularity triple
    and cut-edge flag. ``pad_nodes``/``pad_menu`` pad the node / menu axes
    with neutral single-value menus so fleet buckets can stack problems of
    different sizes (padded nodes are never drawn: the sweep bounds its
    node draw by ``DeviceArrays.n_valid``). ``pad_val`` extends the clamp
    table's value axis to a larger platform's maximum fold value — the
    divisor walk-down is pure node arithmetic, so the extra entries are
    exact (and unreachable: this problem's menus never draw them), which
    lets heterogeneous-platform buckets stack their clamp tables.

    ``clamp[var, node, v]`` is the largest divisor of the node's dimension
    for ``var`` that is at most ``v`` (0 at ``v = 0``; padded nodes are all
    ones). It is computed once per distinct dimension of the graph — a few
    against 3 x n rows — as a divisibility mask over ``v = 0..max_val``
    followed by a running maximum, then gathered back to the rows; the
    counter ``accel.tables.clamp_dims`` adds the number of distinct
    dimensions each build computes.
    """
    graph, backend, platform = \
        problem.graph, problem.backend, problem.platform
    n = len(graph.nodes)
    n_pad = n if pad_nodes is None else int(pad_nodes)

    max_val = max(platform.fold_values())
    if pad_val is not None:
        if pad_val < max_val:
            raise ValueError(f"pad_val={pad_val} < max fold value {max_val}")
        max_val = int(pad_val)
    menu_lists = {}
    max_menu = 1
    for vi, var in enumerate(VARS):
        for j in range(n):
            cands = backend.candidates(graph, j, var, platform)
            menu_lists[(vi, j)] = cands
            max_menu = max(max_menu, len(cands))
    if pad_menu is not None:
        if pad_menu < max_menu:
            raise ValueError(f"pad_menu={pad_menu} < menu size {max_menu}")
        max_menu = int(pad_menu)
    menus = np.ones((3, n_pad, max_menu), np.int64)
    menu_sizes = np.ones((3, n_pad), np.int64)
    for (vi, j), cands in menu_lists.items():
        menus[vi, j, :len(cands)] = cands
        menu_sizes[vi, j] = len(cands)
    # clamp[var, node, v] = set_fold's divisor walk-down of value v, one
    # row per distinct dimension: keep v where it divides, then a running
    # maximum along the value axis
    dims = np.array([[getattr(node, _DIMS[var]) for node in graph.nodes]
                     for var in VARS], np.int64)
    uniq, inv = np.unique(dims, return_inverse=True)
    _metrics.counter("accel.tables.clamp_dims").inc(len(uniq))
    vals = np.arange(max_val + 1, dtype=np.int64)
    divides = uniq[:, None] % np.maximum(vals, 1)[None, :] == 0
    rows = np.maximum.accumulate(np.where(divides, vals, 0), axis=1)
    clamp = np.ones((3, n_pad, max_val + 1), np.int64)
    clamp[:, :n] = rows[inv.reshape(3, n)]
    # kv_fix[j]: largest s_out menu value within the node's KV limit — the
    # on-device repair target for strict-KV violations (see repair_jax)
    kv_fix = np.ones(n_pad, np.int64)
    for j in range(n):
        kvl = graph.nodes[j].kv_limit
        if kvl > 0:
            legal = [c for c in menu_lists[(1, j)] if c <= kvl]
            kv_fix[j] = max(legal) if legal else 1
    gran = tuple(backend.granularity[var] for var in VARS)
    return menus, menu_sizes, clamp, kv_fix, gran, \
        bool(len(graph.cut_edges) > 0)


class DeviceSA:
    """Device-resident multi-chain SA: move tables + the jitted sweep loop.

    One instance per Problem; ``run`` advances a chain-state pytree by
    ``n_sweeps`` sweeps and is resumable (the host can interleave calls
    with wall-clock budget checks). Incumbents are tracked per chain on
    device and read back with ``best_variables``. The whole sweep —
    proposal, on-device repair, evaluation, Metropolis, incumbent update —
    is one ``lax.scan`` program: zero host round-trips mid-run.
    """

    def __init__(self, problem, *, pad_nodes: Optional[int] = None,
                 pad_menu: Optional[int] = None,
                 pad_pairs: Optional[int] = None,
                 pad_vals: Optional[int] = None,
                 pad_lut: Optional[int] = None, tables=None):
        self.problem = problem
        self.jev = JaxEvaluator.from_problem(problem, pad_nodes=pad_nodes,
                                             pad_pairs=pad_pairs,
                                             pad_vals=pad_vals,
                                             pad_lut=pad_lut)
        self.static, self.A = self.jev.static, self.jev.arrays
        self.n_real = len(problem.graph.nodes)
        idt = np.int64 if self.A.batch.dtype == jnp.int64 else np.int32
        if tables is None:
            tables = build_sa_tables(problem, pad_nodes=self.static.n_nodes,
                                     pad_menu=pad_menu)
        menus, menu_sizes, clamp, kv_fix, gran, has_cuts = tables
        self.menus = jnp.asarray(menus, idt)
        self.menu_sizes = jnp.asarray(menu_sizes, idt)
        self.clamp = jnp.asarray(clamp, idt)
        self.kv_fix = jnp.asarray(kv_fix, idt)
        self.gran = gran
        self.has_cut_edges = has_cuts

    # ------------------------------------------------------------------
    @_trace.traced("accel.h2d.sa_state")
    def init_state(self, v0: Variables, ev0, chains: int, seed: int):
        n = self.static.n_nodes
        idt = self.A.batch.dtype
        pad = n - self.n_real
        av = lambda t: np.pad(np.asarray(t, np.int64), (0, pad),
                              constant_values=1)
        si = jnp.broadcast_to(
            jnp.asarray(av(v0.s_in), idt)[None, :], (chains, n))
        so = jnp.broadcast_to(
            jnp.asarray(av(v0.s_out), idt)[None, :], (chains, n))
        kk = jnp.broadcast_to(
            jnp.asarray(av(v0.kern), idt)[None, :], (chains, n))
        cb_row = np.zeros(max(n - 1, 0), bool)
        for c in v0.cuts:
            cb_row[c] = True
        cb = jnp.broadcast_to(jnp.asarray(cb_row)[None, :],
                              (chains, max(n - 1, 0)))
        # commit the dtype explicitly: a weak-typed float here would retrace
        # the sweep program on the first resume (tests assert one trace)
        obj = jnp.full((chains,), float(ev0.objective), self.A.flops.dtype)
        feas = jnp.full((chains,), bool(ev0.feasible))
        return {
            "si": si, "so": so, "kk": kk, "cb": cb,
            "obj": obj, "feas": feas,
            "best_si": si, "best_so": so, "best_kk": kk, "best_cb": cb,
            "best_obj": obj, "best_feas": feas,
            "key": jax.random.PRNGKey(seed),
        }

    def run(self, state, temps, scale: float, cooling: float, k_min: float,
            n_sweeps: int):
        with _metrics.device_dispatch("sa_sweeps", sweeps=n_sweeps):
            return _sa_sweeps(self.static, self.gran, self.has_cut_edges,
                              n_sweeps, self.A, self.menus, self.menu_sizes,
                              self.clamp, self.kv_fix, state, temps, scale,
                              cooling, k_min)

    # ------------------------------------------------------------------
    def best_variables(self, state):
        """Per-chain incumbents as host ``Variables`` + (objective, feasible)."""
        nr = self.n_real
        with _trace.span("accel.d2h.sa_best"):
            si = np.asarray(state["best_si"])[:, :nr]
            so = np.asarray(state["best_so"])[:, :nr]
            kk = np.asarray(state["best_kk"])[:, :nr]
            cb = np.asarray(state["best_cb"])[:, :max(nr - 1, 0)]
            objs = np.asarray(state["best_obj"], np.float64)
            feas = np.asarray(state["best_feas"], bool)
        out = []
        for c in range(si.shape[0]):
            cuts = tuple(int(e) for e in np.nonzero(cb[c])[0])
            out.append((Variables(cuts, tuple(int(x) for x in si[c]),
                                  tuple(int(x) for x in so[c]),
                                  tuple(int(x) for x in kk[c])),
                        float(objs[c]), bool(feas[c])))
        return out


def _masked_choice(key, mask):
    """Uniform index among True entries per row.

    Draws ONE uniform per row and selects the k-th True entry via a
    cumulative count — the draw shape is [rows], independent of the
    (possibly padded) column count, so fleet and per-problem sweeps
    consume identical random streams. Rows with an empty mask return 0 —
    callers gate on the count.
    """
    C = mask.shape[0]
    u = jax.random.uniform(key, (C,))
    cnt = mask.sum(axis=1)
    k = jnp.minimum(jnp.floor(u * cnt).astype(cnt.dtype),
                    jnp.maximum(cnt - 1, 0))
    cum = jnp.cumsum(mask.astype(cnt.dtype), axis=1)
    return jnp.argmax((cum == (k + 1)[:, None]) & mask, axis=1)


def _sa_sweep_step(static: StaticSpec, gran: Tuple[str, str, str],
                   has_cut_edges: bool, A: DeviceArrays, menus, menu_sizes,
                   clamp, kv_fix, scale, cooling, k_min, carry, _):
    """One SA sweep for all chains: propose, repair, evaluate, accept."""
    st, temps = carry
    key, kt, kc1, kc2, kc3, kn, km, kacc = \
        jax.random.split(st["key"], 8)
    si, so, kk, cb = st["si"], st["so"], st["kk"], st["cb"]
    C = si.shape[0]

    # ---------------- cut proposal --------------------------------
    if has_cut_edges:
        removable = cb
        addable = A.cut_allowed[None, :] & ~cb
        n_rem = removable.sum(axis=1)
        n_add = addable.sum(axis=1)
        r2 = jax.random.uniform(kc1, (C,))
        do_rem = (r2 < 0.45) & (n_rem > 0)
        do_add = ~do_rem & (r2 < 0.9) & (n_add > 0)
        do_move = ~do_rem & ~do_add & (n_rem > 0) & (n_add > 0)
        rem_i = _masked_choice(kc2, removable)
        add_i = _masked_choice(kc3, addable)
        E = cb.shape[1]
        oh_rem = jnp.arange(E)[None, :] == rem_i[:, None]
        oh_add = jnp.arange(E)[None, :] == add_i[:, None]
        cb_cut = cb & ~(oh_rem & (do_rem | do_move)[:, None])
        cb_cut = cb_cut | (oh_add & (do_add | do_move)[:, None])
    else:
        cb_cut = cb

    # ---------------- fold proposal (joint triple redraw) ---------
    i = jax.random.randint(kn, (C,), 0, A.n_valid)
    draws = jax.random.randint(km, (8, 3, C), 0, 1 << 30)
    sizes_i = menu_sizes[:, i]                       # [3, C]
    mi = draws % sizes_i[None, :, :]                 # [8, 3, C]
    vals = menus[jnp.arange(3)[None, :, None],
                 i[None, None, :], mi]               # [8, 3, C]
    lut, cap = A.val_lut, A.val_cap
    iv = lut[jnp.minimum(vals, cap)]
    known = (iv >= 0).all(axis=1)
    ok = known & A.real_table[jnp.maximum(iv[:, 0], 0),
                              jnp.maximum(iv[:, 1], 0),
                              jnp.maximum(iv[:, 2], 0)]
    sel = jnp.where(ok.any(axis=0), jnp.argmax(ok, axis=0), 7)
    v3 = jnp.take_along_axis(vals, sel[None, None, :], 0)[0]   # [3, C]

    p_si, p_so, p_kk = _scatter_triple(static, gran, A, clamp,
                                       si, so, kk, cb, i, v3)
    # on-device repair: masked clamp-and-propagate (no host round-trip)
    p_si, p_so, p_kk = repair_jax(static, A, kv_fix, p_si, p_so, p_kk, cb)

    # ---------------- select + evaluate ---------------------------
    r_type = jax.random.uniform(kt, (C,))
    is_cut = (r_type < 0.25) if has_cut_edges \
        else jnp.zeros((C,), bool)
    p_si = jnp.where(is_cut[:, None], si, p_si)
    p_so = jnp.where(is_cut[:, None], so, p_so)
    p_kk = jnp.where(is_cut[:, None], kk, p_kk)
    p_cb = jnp.where(is_cut[:, None], cb_cut, cb)
    res = _eval_core(static, A, p_si, p_so, p_kk, p_cb)
    p_obj = res["objective"].astype(st["obj"].dtype)
    p_feas = res["feasible"]

    # ---------------- Metropolis (Eq. 11) -------------------------
    u = jax.random.uniform(kacc, (C,))
    delta = (st["obj"] - p_obj) / scale
    psi = jnp.exp(jnp.minimum(0.0, delta / temps))
    accept = p_feas & (psi >= u)
    acc2 = accept[:, None]
    st = dict(st)
    st["si"] = jnp.where(acc2, p_si, si)
    st["so"] = jnp.where(acc2, p_so, so)
    st["kk"] = jnp.where(acc2, p_kk, kk)
    st["cb"] = jnp.where(acc2, p_cb, cb)
    st["obj"] = jnp.where(accept, p_obj, st["obj"])
    st["feas"] = jnp.where(accept, p_feas, st["feas"])

    # incumbents consider every proposal, accepted or not (a feasible
    # evaluation always beats an infeasible incumbent)
    better = (p_feas & ~st["best_feas"]) \
        | ((p_feas == st["best_feas"]) & (p_obj < st["best_obj"]))
    b2 = better[:, None]
    st["best_si"] = jnp.where(b2, p_si, st["best_si"])
    st["best_so"] = jnp.where(b2, p_so, st["best_so"])
    st["best_kk"] = jnp.where(b2, p_kk, st["best_kk"])
    st["best_cb"] = jnp.where(b2, p_cb, st["best_cb"])
    st["best_obj"] = jnp.where(better, p_obj, st["best_obj"])
    st["best_feas"] = st["best_feas"] | p_feas
    st["key"] = key
    temps = jnp.maximum(k_min, temps * cooling)   # lockstep ladder cool
    return (st, temps), (st["best_obj"], st["best_feas"])


def _sa_scan(static: StaticSpec, gran, has_cut_edges: bool, n_sweeps: int,
             A, menus, menu_sizes, clamp, kv_fix, state, temps, scale,
             cooling, k_min):
    """Un-jitted scan driver shared by the per-problem jit and the fleet
    vmap; returns (state, temps, traces)."""
    step = functools.partial(_sa_sweep_step, static, gran, has_cut_edges,
                             A, menus, menu_sizes, clamp, kv_fix,
                             scale, cooling, k_min)
    (state, temps), traces = jax.lax.scan(
        step, (state, temps), None, length=n_sweeps)
    return state, temps, traces


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _sa_sweeps(static: StaticSpec, gran: Tuple[str, str, str],
               has_cut_edges: bool, n_sweeps: int,
               A: DeviceArrays, menus, menu_sizes, clamp, kv_fix,
               state, temps, scale, cooling, k_min):
    """Advance all chains by ``n_sweeps``; returns (state, temps, traces)."""
    TRACE_COUNTS["sa_sweeps"] += 1
    return _sa_scan(static, gran, has_cut_edges, n_sweeps, A, menus,
                    menu_sizes, clamp, kv_fix, state, temps, scale,
                    cooling, k_min)


# ----------------------------------------------------------------------
# rule-based (Algorithm 2): the whole greedy descent as one device loop
# ----------------------------------------------------------------------

def _rb_step(static: StaticSpec, gran: Tuple[str, str, str],
             A: DeviceArrays, menus, menu_sizes, clamp, cb_row, part_mask,
             pidx, amort, si, so, kk, blocked, points):
    """One Algorithm-2 greedy step, entirely on device.

    Mirrors the scalar ``optimise_partition`` step exactly: pick the
    slowest unblocked node of the partition, enumerate its joint fold menu
    (s_in-major, the scalar probe order), construct every probe through
    the scoped scatter + propagate, evaluate probes WITH the incumbent as
    row 0 (both sides of every comparison carry the same rounding), and
    select the feasible, strictly-improving probe with the
    lexicographically smallest (collective-bytes, residency) resource
    delta — earliest probe wins ties, as in the scalar loop. A step with
    no winning probe blocks the node; a winning move unblocks the node's
    tying scopes.
    """
    n = static.n_nodes
    idt = A.batch.dtype
    fdt = A.flops.dtype
    iota_n = jnp.arange(n, dtype=idt)
    mm = menus.shape[-1]
    B = mm * mm * mm

    # ---- slowest unblocked node of the partition ---------------------
    ev0 = _eval_core(static, A, si[None, :], so[None, :], kk[None, :],
                     cb_row[None, :])
    cand = part_mask & ~blocked
    nt = jnp.where(cand, ev0["node_times"][0], -jnp.inf)
    j = jnp.argmax(nt).astype(idt)

    # ---- the node's joint fold menu, in scalar probe order -----------
    p = jnp.arange(B, dtype=idt)
    a, b, c = p // (mm * mm), (p // mm) % mm, p % mm
    v3 = jnp.stack([menus[0, j, a], menus[1, j, b], menus[2, j, c]])
    in_menu = (a < menu_sizes[0, j]) & (b < menu_sizes[1, j]) \
        & (c < menu_sizes[2, j])
    cur = jnp.stack([si[j], so[j], kk[j]])
    not_cur = (v3 != cur[:, None]).any(axis=0)
    lut, cap = A.val_lut, A.val_cap
    iv = lut[jnp.minimum(v3, cap)]
    known = (iv >= 0).all(axis=0)
    realiz = known & A.real_table[jnp.maximum(iv[0], 0),
                                  jnp.maximum(iv[1], 0),
                                  jnp.maximum(iv[2], 0)]
    probe_ok = in_menu & not_cur & realiz                      # [B]
    n_cands = probe_ok.sum().astype(points.dtype)

    # ---- construct + evaluate (incumbent as row 0) -------------------
    E = cb_row.shape[0]
    cbB = jnp.broadcast_to(cb_row[None, :], (B, E))
    p_si, p_so, p_kk = _scatter_triple(
        static, gran, A, clamp,
        jnp.broadcast_to(si[None, :], (B, n)),
        jnp.broadcast_to(so[None, :], (B, n)),
        jnp.broadcast_to(kk[None, :], (B, n)),
        cbB, jnp.full((B,), j, idt), v3)
    SI = jnp.concatenate([si[None, :], p_si], axis=0)          # [B+1, n]
    SO = jnp.concatenate([so[None, :], p_so], axis=0)
    KK = jnp.concatenate([kk[None, :], p_kk], axis=0)
    res = _eval_core(static, A, SI, SO, KK,
                     jnp.broadcast_to(cb_row[None, :], (B + 1, E)))

    # ---- decision quantities (the scalar b_cost / resource vector) ---
    # A probe is compared with the incumbent (row 0) by the sum of its
    # per-node (and per-edge) differences, not by the difference of two
    # totals: a probe differs from the incumbent in a few nodes, and a
    # float32 total would lose a change below its resolution that the
    # float64 reference sees (a light node's move inside a heavy
    # partition). It improves when its cost falls by more than the
    # threshold plus the rounding of the terms that changed, so that a
    # change the float64 reference finds to be nil is nil here too.
    def delta(x, mask):
        """(sum of row r's differences from row 0, their magnitude)."""
        d = jnp.where(mask[None, :], x - x[0:1], 0.0)
        mag = jnp.where(d != 0, jnp.abs(x) + jnp.abs(x[0:1]), 0.0)
        return d.sum(axis=1), mag.sum(axis=1)

    pid1 = jnp.concatenate(
        [jnp.zeros((1,), idt), jnp.cumsum(cb_row.astype(idt))])
    if static.exec_model == "spmd":
        # partition ``pidx`` of the design (repair may have cut ``part``)
        in_p = pid1 == pidx
        d_n, m_n = delta(res["node_times"], in_p)
        d_e, m_e = delta(res["edge_times"], in_p[:-1] & in_p[1:])
        d_t, m_t = d_n + d_e, m_n + m_e
    else:
        d_t, m_t = delta(jnp.take(res["part_times"], pidx, axis=1)[:, None],
                         jnp.ones((1,), bool))
    d_w, m_w = delta(A.weight_bytes[None, :] / SO.astype(fdt), part_mask)
    later = pidx > 0                                   # + t_conf(part)
    zero = jnp.zeros((), fdt)
    d_cost = d_t + jnp.where(later, amort * (d_w / A.dma_bw), zero)
    mag = m_t + jnp.where(later, amort * (m_w / A.dma_bw), zero)
    rounding = 16.0 * jnp.finfo(fdt).eps * mag
    coll = res["node_collective"].sum(axis=1)
    resd = res["node_resident"].sum(axis=1)
    dr0 = coll - coll[0]
    dr1 = resd - resd[0]
    improving = res["feasible"] & (d_cost < -1e-15 - rounding)
    valid = improving & jnp.concatenate(
        [jnp.zeros((1,), bool), probe_ok])
    any_valid = valid.any()

    # lexicographic (dr0, dr1) argmin over valid rows, first index wins —
    # exactly the scalar `dr < best[0]` strict-less update in probe order
    d0 = jnp.where(valid, dr0, jnp.inf)
    m0 = d0.min()
    tie0 = valid & (dr0 == m0)
    d1 = jnp.where(tie0, dr1, jnp.inf)
    m1 = d1.min()
    sel = jnp.argmax(tie0 & (dr1 == m1))

    # ---- apply the move / block the node -----------------------------
    si2 = jnp.where(any_valid, jnp.take(SI, sel, axis=0), si)
    so2 = jnp.where(any_valid, jnp.take(SO, sel, axis=0), so)
    kk2 = jnp.where(any_valid, jnp.take(KK, sel, axis=0), kk)
    same_part = pid1 == pid1[j]
    sg_j = A.scan_group[j]
    oh_j = iota_n == j
    unblock = jnp.zeros(n, bool)
    for g in gran:                       # static: the Python loop unrolls
        # NOTE: scope here is the raw Backend.scope — no decode split-KV
        # exclusion, matching the scalar unblock loop
        unblock = unblock | _scope_mask(g, same_part, A.scan_group, sg_j,
                                        oh_j)
    blocked2 = jnp.where(any_valid, blocked & ~unblock, blocked | oh_j)
    return si2, so2, kk2, blocked2, points + n_cands


def _rb_descend_core(static: StaticSpec, gran: Tuple[str, str, str],
                     A: DeviceArrays, menus, menu_sizes, clamp,
                     si, so, kk, cb_row, part_mask, pidx, amort, cap):
    """Algorithm 2 lines 1-8 as ONE device loop: the greedy descent runs
    as a ``lax.while_loop`` whose body is the fused probe-construct →
    evaluate → argmax-select step (``_rb_step``), terminating — exactly
    like the scalar loop — when every partition node is blocked or the
    step cap (``max(512, 16·|part|)``, host-computed data) is reached.
    Returns (si, so, kk, probe_points). ``cap == 0`` makes the whole
    descent a no-op, which is how the vmapped fleet masks lanes whose
    problem has no pending descent (and how lanes that converge early
    idle while the rest of the bucket finishes)."""
    n = static.n_nodes
    idt = A.batch.dtype

    def cond(carry):
        si, so, kk, blocked, points, step = carry
        return (step < cap) & (part_mask & ~blocked).any()

    def body(carry):
        si, so, kk, blocked, points, step = carry
        si, so, kk, blocked, points = _rb_step(
            static, gran, A, menus, menu_sizes, clamp, cb_row, part_mask,
            pidx, amort, si, so, kk, blocked, points)
        return (si, so, kk, blocked, points, step + 1)

    carry = (si, so, kk, jnp.zeros(n, bool), jnp.zeros((), idt),
             jnp.zeros((), idt))
    si, so, kk, _, points, _ = jax.lax.while_loop(cond, body, carry)
    return si, so, kk, points


@functools.partial(jax.jit, static_argnums=(0, 1))
def _rb_descend(static: StaticSpec, gran: Tuple[str, str, str],
                A: DeviceArrays, menus, menu_sizes, clamp, req, amort):
    """One single-problem descent from one packed request vector.

    ``req`` is ``si | so | kk | cb_row | part_mask | pidx | cap`` in the
    device int dtype, the masks as 0/1 (``DeviceRuleBased.descend``
    packs it); the offsets follow from ``static.n_nodes``. Returns one
    vector ``si | so | kk | points`` in the same dtype, so a descent is
    one copy each way."""
    TRACE_COUNTS["rb_descend"] += 1
    n = static.n_nodes
    e = max(n - 1, 0)
    si, so, kk = req[:n], req[n:2 * n], req[2 * n:3 * n]
    cb_row = req[3 * n:3 * n + e] != 0
    part_mask = req[3 * n + e:4 * n + e] != 0
    pidx, cap = req[4 * n + e], req[4 * n + e + 1]
    si, so, kk, points = _rb_descend_core(
        static, gran, A, menus, menu_sizes, clamp, si, so, kk, cb_row,
        part_mask, pidx, amort, cap)
    return jnp.concatenate([si, so, kk, points[None]])


class DeviceRuleBased:
    """Device-resident Algorithm-2 greedy descent for one Problem.

    ``descend(v, part)`` answers one ``rule_based._algorithm2`` request:
    the whole greedy descent of that partition is ONE jitted
    ``lax.while_loop`` call (``_rb_descend``) — probe construction,
    evaluation, selection and the step loop never leave the device — and
    the chosen move sequence is identical to the scalar reference (the
    decision quantities agree to float tolerance and ties break in the
    same probe order; tests assert the resulting designs match bitwise).
    Reuses the SA move tables (``build_sa_tables``): menus, sizes and the
    per-node clamp are exactly ``backend.candidates`` + ``set_fold``'s
    divisor walk-down. Padding (``pad_nodes``/``pad_menu``/...) follows
    the fleet stacking contract; padded nodes are never in ``part`` and
    padded menu slots fail the in-menu test, so they cannot be probed.
    """

    def __init__(self, problem, *, pad_nodes: Optional[int] = None,
                 pad_menu: Optional[int] = None,
                 pad_pairs: Optional[int] = None,
                 pad_vals: Optional[int] = None,
                 pad_lut: Optional[int] = None, tables=None):
        self.problem = problem
        self.jev = JaxEvaluator.from_problem(problem, pad_nodes=pad_nodes,
                                             pad_pairs=pad_pairs,
                                             pad_vals=pad_vals,
                                             pad_lut=pad_lut)
        self.static, self.A = self.jev.static, self.jev.arrays
        self.n_real = len(problem.graph.nodes)
        idt = np.int64 if self.A.batch.dtype == jnp.int64 else np.int32
        if tables is None:
            tables = build_sa_tables(problem, pad_nodes=self.static.n_nodes,
                                     pad_menu=pad_menu)
        menus, menu_sizes, clamp, _kv_fix, gran, _ = tables
        self.menus = jnp.asarray(menus, idt)
        self.menu_sizes = jnp.asarray(menu_sizes, idt)
        self.clamp = jnp.asarray(clamp, idt)
        self.gran = gran
        # Eq. 3/4 reconfiguration amortisation, as in optimise_partition;
        # the float is what the fleet stacks, the device scalar is what
        # ``descend`` passes (a constant of the problem, copied once)
        self.amort = (1.0 if problem.objective == "latency"
                      else 1.0 / max(problem.batch_amortisation, 1))
        self.amort_dev = jax.device_put(
            np.asarray(self.amort, self.A.flops.dtype))

    # ------------------------------------------------------------------
    def pack_request(self, v: Variables, part):
        """Host -> device lowering of one descent request (fleet-shared)."""
        n = self.static.n_nodes
        pad = n - self.n_real
        av = lambda t: np.pad(np.asarray(t, np.int64), (0, pad),
                              constant_values=1)
        cb_row = np.zeros(max(n - 1, 0), bool)
        for cut in v.cuts:
            cb_row[cut] = True
        part_mask = np.zeros(n, bool)
        part_mask[list(part)] = True
        pidx = sum(1 for cut in v.cuts if cut < part[0])
        cap = max(512, 16 * len(part))
        return (av(v.s_in), av(v.s_out), av(v.kern), cb_row, part_mask,
                pidx, cap)

    def pack_descent(self, v: Variables, part) -> np.ndarray:
        """``pack_request`` as the one vector ``_rb_descend`` reads:
        ``si | so | kk | cb_row | part_mask | pidx | cap`` in the device
        int dtype, the masks as 0/1."""
        si, so, kk, cb_row, part_mask, pidx, cap = self.pack_request(v, part)
        return np.concatenate((si, so, kk, cb_row, part_mask, (pidx, cap)),
                              dtype=self.A.batch.dtype)

    def unpack(self, v: Variables, o_si, o_so, o_kk, pts):
        nr = self.n_real
        v2 = Variables(v.cuts,
                       tuple(int(x) for x in np.asarray(o_si)[:nr]),
                       tuple(int(x) for x in np.asarray(o_so)[:nr]),
                       tuple(int(x) for x in np.asarray(o_kk)[:nr]))
        self.problem.note_batch_evals(int(pts))
        return v2, int(pts)

    def descend(self, v: Variables, part):
        """One descent, one copy each way: pack the request into one
        vector and copy it to the device (``accel.h2d.rb_descend``),
        enqueue ``_rb_descend`` (``accel.dispatch.rb_descend``), then one
        blocking readback of the packed answer (``accel.d2h.rb_descend``),
        which absorbs the device time. ``accel.transfers.rb_descend``
        counts both copies."""
        n = self.static.n_nodes
        transfers = _metrics.counter("accel.transfers.rb_descend")
        with _trace.span("accel.h2d.rb_descend"):
            req = jax.device_put(self.pack_descent(v, part))
            transfers.inc()
        with _metrics.device_dispatch("rb_descend", part=len(part)):
            out = _rb_descend(self.static, self.gran, self.A, self.menus,
                              self.menu_sizes, self.clamp, req,
                              self.amort_dev)
        with _trace.span("accel.d2h.rb_descend"):
            ans = np.asarray(out)
            transfers.inc()
        return self.unpack(v, ans[:n], ans[n:2 * n], ans[2 * n:3 * n],
                           ans[3 * n])
