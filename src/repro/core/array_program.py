"""The batched cost model: one array program for the numpy and jax engines.

``eval_core`` evaluates N candidate designs — fold arrays
``(s_in, s_out, kern)[N, n]`` plus a cut bitmask ``[N, n-1]`` — against a
lowered problem (``core/accel/lowering.py``: a ``StaticSpec`` and a
``DeviceArrays`` record) as pure elementwise ops over the node axis:
roofline terms, collective bytes, Eq. 6 residency, constraint masks,
partition times and the Eq. 5 objective. It is written against an array
namespace ``xp``:

  numpy engine  ``BatchedEvaluator.evaluate_batch`` (``core/batched_eval.py``)
                calls it with ``xp=numpy`` on the float64/int64 host record.
  jax engine    ``core/accel/eval_jax._eval_core`` calls it with
                ``xp=jax.numpy``; the jitted brute-force, annealing,
                rule-based and fleet programs trace it.

Every kind-specific term is a ``where`` over a kind mask held in the record
as data, so adding ``0.0`` on the unmasked columns is exact and one traced
program serves any architecture, and padded columns (``node_valid``)
contribute exactly zero to every reduction. Platform scalars, the
realisability cube and the objective selector are data too.

The engine supplies what differs between the two: the segment reductions
over partitions and the row reductions of the one-partition case (``seg``:
``batched_eval.NumpySegments``, ``eval_jax._JaxSegments``), and, on the host
only, a realisability test for fold menus too large for a cube
(``realizable``). The scalar reference stays ``core/perfmodel.py`` +
``core/objectives.py``; tests/test_batched_eval.py holds the numpy engine
to it at 1e-9.

This module imports no array library: ``xp`` is the engine's.
"""
from __future__ import annotations

from repro.core.perfmodel import (
    BF16,
    TRAIN_STATE_MULT,
    ZERO1_RESIDENT,
    ZERO1_SHARDED,
)


def _frac(x):
    return (x - 1.0) / x


def _madd(total, mask, term, *, xp):
    """Masked column add: exact (+0.0 off-mask), vmap/pad-safe."""
    return total + xp.where(mask[None, :], term, xp.zeros_like(term))


def _collective_bytes(static, A, si, so, kk, sif, sof, kkf, b_in, *, xp):
    """Per-chip collective bytes of every node (perfmodel._collective_bytes,
    mask-driven)."""
    fdt = sif.dtype
    train_mult = 2.0 if static.train else 1.0
    total = xp.zeros_like(sif)
    batchf = A.batch.astype(fdt)
    rowsf = A.rows.astype(fdt)
    colsf = A.cols.astype(fdt)
    fmf = A.fm_width.astype(fdt)
    rows_eff = xp.ones_like(rowsf) if static.decode else rowsf

    fm_shard = (batchf * rows_eff * fmf)[None, :] * BF16 / (b_in * kkf)

    total = _madd(total, A.m_tp, 2.0 * _frac(sof) * fm_shard * train_mult,
                  xp=xp)

    tokens_shard = (batchf * rows_eff)[None, :] / (b_in * kkf)
    fanout = xp.maximum(A.ep_topk, 1).astype(fdt)
    total = _madd(total, A.m_ep,
                  2.0 * tokens_shard * (fanout * fmf)[None, :] * BF16
                  * _frac(sof) * train_mult, xp=xp)

    total = _madd(total, A.m_vocab,
                  2.0 * _frac(sof) * fm_shard * train_mult, xp=xp)

    if static.decode:
        vhead = (colsf * batchf)[None, :] * BF16 / kkf * _frac(sof)
    else:
        # distributed softmax stats: constant in s_out, so the scalar
        # path's s_out > 1 guard must be kept explicitly
        vh = 2.0 * 8.0 * (batchf * rowsf)[None, :] / (b_in * kkf)
        vhead = xp.where(so > 1, vh, xp.zeros_like(vh))
    total = _madd(total, A.m_vhead, vhead, xp=xp)

    # sequence/context parallelism (s_in > 1): all terms carry the
    # (s_in-1)/s_in factor, vanishing at s_in = 1
    kvlf = A.kv_limit.astype(fdt)
    kv_div = xp.where(A.kv_limit[None, :] > 0,
                      xp.minimum(sof, kvlf[None, :]),
                      xp.maximum(sof, 1.0))
    # latent attention combines its partials kv_lora_rank wide
    dh = xp.where(A.m_latent, A.latent_dim.astype(fdt),
                  fmf / xp.maximum(colsf, 1.0))
    total = _madd(total, A.internal,
                  (batchf[None, :] / kkf) * colsf[None, :]
                  / xp.maximum(kv_div, 1.0) * ((dh + 2.0) * 4.0)[None, :]
                  * _frac(sif), xp=xp)
    # the latent cache is whole on every head fold
    ring_div = xp.where(A.m_latent[None, :], xp.ones_like(kv_div), kv_div)
    total = _madd(total, A.m_kv,
                  A.kv_bytes[None, :] / (ring_div * kkf) * _frac(sif)
                  * train_mult, xp=xp)
    total = _madd(total, A.m_carry,
                  A.carry_bytes[None, :] / kkf * _frac(sif) * train_mult,
                  xp=xp)

    # data-parallel gradient all-reduce (per step, ring over k)
    if static.train:
        grad = A.weight_bytes / sof * 2.0 * static.grad_compression
        total = total + 2.0 * _frac(kkf) * grad
    return total


def _realizable(static, A, si, so, kk, *, xp):
    """Mesh realisability of every (s_in, s_out, kern) triple, looked up in
    the record's cube over the platform fold menu."""
    cap = A.val_cap                           # sentinel lut slot (-1)
    lut = A.val_lut
    ia = lut[xp.minimum(si, cap)]
    ib = lut[xp.minimum(so, cap)]
    ic = lut[xp.minimum(kk, cap)]
    known = (ia >= 0) & (ib >= 0) & (ic >= 0)
    return known & A.real_table[xp.maximum(ia, 0),
                                xp.maximum(ib, 0),
                                xp.maximum(ic, 0)]


def eval_core(static, A, si, so, kk, cb, single_partition: bool = False,
              *, xp, seg, realizable=None):
    """The batched array program: [N, n] fold arrays + [N, n-1] cut bitmask
    -> per-candidate results (a dict of ``xp`` arrays).

    ``single_partition`` is a trace-time promise that every row of ``cb``
    is all-False (e.g. a brute-force chunk of the no-cut set): the
    partition machinery collapses to one reduction over the node axis.
    ``seg`` is the engine's reductions (``batched_eval.NumpySegments``,
    ``eval_jax._JaxSegments``).
    ``realizable``, when given, replaces the cube lookup: a host callable
    ``(si, so, kk) -> bool [N, n]`` for records without a cube."""
    n = static.n_nodes
    N = si.shape[0]
    fdt = A.flops.dtype
    idt = A.batch.dtype
    si = si.astype(idt)
    so = so.astype(idt)
    kk = kk.astype(idt)
    cb = cb.astype(bool)
    sif = si.astype(fdt)
    sof = so.astype(fdt)
    kkf = kk.astype(fdt)

    # ---------------- node roofline (perfmodel.node_eval) ----------
    c = sif * sof * kkf
    b_in = xp.where(A.internal[None, :], xp.ones((), fdt), sif)
    compute_s = (A.flops / c) / (A.peak_flops * static.mxu_efficiency)

    w_per_chip = A.weight_bytes / sof
    act_per_chip = A.act_bytes / (b_in * kkf)
    inner_per_chip = A.inner_bytes / c

    # _state_sharding (KV sharding applies on attention-kind columns)
    kvlf = A.kv_limit.astype(fdt)
    kv_div_a = xp.where(A.kv_limit[None, :] > 0,
                        xp.minimum(sof, kvlf[None, :]), sof)
    # latent KV shards over (k, s_in) only: one vector per token
    state_div = xp.where(A.m_attn[None, :],
                         kkf * xp.maximum(kv_div_a, 1.0) * sif,
                         xp.where(A.m_latent[None, :], kkf * sif,
                                  kkf * sof))
    state_repl = xp.where(
        A.m_attn[None, :] & (A.kv_limit[None, :] > 0)
        & (so > A.kv_limit[None, :]),
        sof / kv_div_a, xp.ones_like(sof))
    state_per_chip = A.state_bytes * state_repl / state_div

    train_mult = 3.0 if static.train else 1.0
    hbm = (act_per_chip + inner_per_chip) * train_mult
    if static.train:
        hbm = hbm + 2.0 * w_per_chip
    else:
        hbm = hbm + xp.where(A.weight_stream, w_per_chip,
                             xp.zeros_like(w_per_chip))
        hbm = hbm + state_per_chip
    memory_s = hbm / A.hbm_bw

    coll = _collective_bytes(static, A, si, so, kk, sif, sof, kkf, b_in,
                             xp=xp)
    collective_s = coll / A.ici_bw * (1.0 - static.overlap_collectives)

    # ---------------- residency (Eq. 6) ----------------------------
    if static.train:
        if static.zero1:
            resident = w_per_chip * ZERO1_RESIDENT \
                + w_per_chip * ZERO1_SHARDED / kkf
        else:
            resident = w_per_chip * TRAIN_STATE_MULT
        stash_div = sif * kkf
        if static.seq_parallel_stash:
            stash_div = stash_div * xp.maximum(sof, 1.0)
        fm = A.node_d / BF16                   # batch*rows*fm_width, exact
        resident = resident + fm * BF16 / stash_div
        resident = _madd(resident, A.m_head,
                         3.0 * A.inner_bytes[None, :]
                         / (b_in * kkf * xp.maximum(sof, 1.0)), xp=xp)
    else:
        rows = (xp.ones_like(A.rows) if static.decode else A.rows).astype(fdt)
        resident = w_per_chip + state_per_chip \
            + 2.0 * (A.batch.astype(fdt) * rows * A.fm_width.astype(fdt)
                     * BF16)[None, :] / (b_in * kkf)

    node_time = xp.maximum(xp.maximum(compute_s, memory_s), collective_s)

    # ---------------- partition structure ---------------------------
    if n > 1:
        edge_valid = A.node_valid[:-1] & A.node_valid[1:]
        mism = ((b_in[:, :-1] != b_in[:, 1:]) | (kk[:, :-1] != kk[:, 1:])) \
            & edge_valid[None, :]
    else:
        mism = xp.zeros((N, 0), bool)
    iota_n = xp.arange(n, dtype=idt)
    # padded columns are neutral everywhere EXCEPT the streaming chip
    # count (their fold product is 1, not 0) — zero them explicitly there
    c_eff = xp.where(A.node_valid[None, :], c, xp.zeros_like(c))
    # resharding collectives at intra-partition layout changes (backends
    # without inter matching pay them), per edge
    if not static.inter_matching and n > 1:
        edge_t = xp.where(~cb & mism, A.reshard_full[:-1] / A.ici_bw, 0.0)
    else:
        edge_t = xp.zeros((N, max(n - 1, 0)), fdt)

    if single_partition:
        # every candidate is one partition — no segment reductions, no
        # reconfiguration, no boundary staging
        pid = xp.zeros((N, n), idt)
        nparts = xp.ones((N,), idt)
        part_valid = iota_n[None, :] < 1
        t0 = node_time.max(axis=1) if static.exec_model == "streaming" \
            else seg.row_sum(node_time)
        if not static.inter_matching and n > 1:
            t0 = t0 + seg.row_sum(xp.where(
                mism, A.reshard_full[:-1] / A.ici_bw, 0.0))
        t_part = seg.first_column(t0, n)
        reconf = xp.zeros((N,), fdt)
        sum_t = t0
    else:
        pid = xp.concatenate(
            [xp.zeros((N, 1), idt), xp.cumsum(cb.astype(idt), axis=1)],
            axis=1)
        nparts = pid[:, -1] + 1
        part_valid = iota_n[None, :] < nparts[:, None]
        seg_sum, seg_max, edge_sum = seg.reducers(pid, iota_n, fdt)

        if static.exec_model == "streaming":
            t_base = xp.where(part_valid, seg_max(node_time), 0.0)
        else:
            t_base = seg_sum(node_time)

        t_part = t_base
        if not static.inter_matching and n > 1:
            t_part = t_part + edge_sum(edge_t)
        t_part = xp.where(part_valid, t_part, 0.0)

        # reconfiguration (Eq. 3): first configuration is pre-loaded
        w_part = seg_sum(w_per_chip)
        t_conf_part = A.reconf_fixed_s + w_part / A.dma_bw
        later = part_valid & (iota_n[None, :] >= 1)
        reconf = xp.sum(xp.where(later, t_conf_part, 0.0), axis=1)

        sum_t = t_part.sum(axis=1)
    latency = sum_t + reconf
    # objective configuration is per-problem DATA (lowering.py): both Eq. 3
    # and Eq. 4 are computed and a traced where selects — so one executable
    # serves any (objective, batch_amortisation) mix in a fleet bucket
    Bam = A.batch_amortisation
    thr_time = Bam * sum_t + reconf
    throughput = xp.where(thr_time > 0,
                          Bam / xp.where(thr_time > 0, thr_time, 1.0), 0.0)
    obj = xp.where(A.obj_latency, latency, -throughput)

    # ---------------- constraints ----------------------------------
    bad = xp.zeros(N, bool)
    # channel factor (Eq. 8) + cut legality + mesh realisability
    if n > 1:
        bad |= (cb & ~A.cut_allowed[None, :]).any(axis=1)
    bad |= (A.rows % si != 0).any(axis=1)
    bad |= (A.col_div % so != 0).any(axis=1)
    bad |= (A.batch % kk != 0).any(axis=1)
    if static.strict_kv:
        bad |= ((A.kv_limit > 0) & (so > A.kv_limit)).any(axis=1)
    if realizable is None:
        bad |= ~_realizable(static, A, si, so, kk, xp=xp).all(axis=1)
    else:
        bad |= ~realizable(si, so, kk).all(axis=1)
    # intra matching (Eq. 9)
    if static.intra_matching:
        bad |= (A.elementwise & (si != so)).any(axis=1)
    # inter matching (Eq. 10), partition-local
    if static.inter_matching and n > 1:
        bad |= (~cb & mism).any(axis=1)
    # scan tying, partition-local (consecutive member pairs; the device
    # record pads them with (0, 0) self-pairs, which can never differ)
    if static.scan_tying:
        a, b = A.pair_a, A.pair_b
        differ = (si[:, a] != si[:, b]) | (so[:, a] != so[:, b]) \
            | (kk[:, a] != kk[:, b])
        differ &= pid[:, a] == pid[:, b]
        bad |= differ.any(axis=1)
    # resource (Eq. 6) + streaming chip budget + bandwidth (Eq. 7)
    if single_partition:
        bad |= seg.row_sum(resident) > A.hbm_bytes
        if static.exec_model == "streaming":
            bad |= seg.row_sum(c_eff) > A.chips
        # single partition: no boundary staging, bandwidth never binds
    else:
        res_part = seg_sum(resident)
        multi = nparts > 1
        start = xp.concatenate([xp.ones((N, 1), bool), cb], axis=1)
        # the last partition ends at the last REAL node (padded columns
        # stage nothing)
        end = xp.concatenate([cb, xp.zeros((N, 1), bool)], axis=1) \
            | (iota_n == A.n_valid - 1)[None, :]
        d_io = seg_sum(A.node_d[None, :]
                       * (start.astype(fdt) + end.astype(fdt)))
        res_tot = res_part + xp.where(multi[:, None],
                                      d_io / A.chips, 0.0)
        bad |= (part_valid & (res_tot > A.hbm_bytes)).any(axis=1)
        if static.exec_model == "streaming":
            chips_part = seg_sum(c_eff)
            bad |= (part_valid & (chips_part > A.chips)).any(axis=1)
        # bandwidth uses the pre-resharding partition interval, exactly
        # like constraints.check_bandwidth
        bw = A.hbm_bw * A.chips
        bw_bad = multi[:, None] & part_valid & (t_base > 0) \
            & (d_io / xp.where(t_base > 0, t_base, 1.0) > bw)
        bad |= bw_bad.any(axis=1)

    return {
        "objective": obj, "feasible": ~bad, "latency": latency,
        "throughput": throughput, "part_times": t_part, "nparts": nparts,
        "reconf_time": reconf, "node_resident": resident,
        "node_times": node_time, "node_collective": coll,
        "edge_times": edge_t,
    }

