"""Jitted batched design-point evaluation (the JAX port of batched_eval).

``_eval_core`` is a line-for-line port of
``BatchedEvaluator.evaluate_batch`` + ``_collective_bytes`` onto jnp: pure
elementwise ops, kind-masked column terms, and segmented partition
reductions via dense one-hot contractions (or the Pallas kernel in
``pallas_segred.py`` when ``StaticSpec.use_pallas`` is set). The numpy
engine always takes the general segmented path here — its no-cut fast path
is a host-side shortcut with identical semantics, so agreement holds across
both layouts.

Unlike the numpy engine (which slices static kind-column index sets), every
kind-specific term here is a ``jnp.where`` over a mask stored in
``DeviceArrays``. Adding ``0.0`` on the unmasked columns is exact, so the
masked form is bitwise identical to the sliced form — and because the mask
is *data*, the same traced program serves any architecture: the fleet
engine (``fleet.py``) vmaps this function across a stacked problem axis,
and padded columns (``DeviceArrays.node_valid``) contribute exactly zero
to every reduction. Platform scalars (resource limits, bandwidths,
``chips``, the realisability lut sentinel) are likewise read from
``DeviceArrays`` — scalar operands, so each use broadcasts exactly like
the host engine's Python floats and the program is bitwise independent of
*which* platform supplied them: one executable serves any platform, and
vmapping over stacked per-problem scalar rows serves a heterogeneous
(model, platform) portfolio.

Entry points are module-level and take ``(static, arrays, ...)`` so the XLA
executable caches across Problem instances (see lowering.py). Large integer
products (batch x rows x fm_width) are formed in the float dtype to stay
safe under int32 (the default device int width without x64).

Precision contract (tests/test_accel_engine.py):
  float32 (default)   objective/times/residency agree with the scalar
                      reference to ~1e-5 relative; feasibility is exact on
                      the example spaces (constraints are integer-exact or
                      far from their float thresholds).
  float64 (x64 on)    1e-9 agreement, matching the numpy engine's contract.
"""
from __future__ import annotations

import functools
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.accel.lowering import (
    DeviceArrays,
    StaticSpec,
    lower_program,
)
from repro.core.batched_eval import BatchResult
from repro.core.perfmodel import (
    BF16,
    TRAIN_STATE_MULT,
    ZERO1_RESIDENT,
    ZERO1_SHARDED,
)


#: incremented inside jitted function bodies — i.e. once per TRACE, not per
#: call. The no-recompile tests (``assert_max_traces`` in tests/conftest.py)
#: use this to assert executables are shared across problems, platforms and
#: objectives. ``search_loops``/``fleet`` re-export and tick the same
#: mapping. Since PR 7 the ledger lives in the telemetry registry
#: (``repro.obs.metrics``) as a dict-shaped view over counters; this module
#: stays its historic import home.
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

TRACE_COUNTS = _metrics.TRACE_COUNTS


# ----------------------------------------------------------------------
# the traced array program
# ----------------------------------------------------------------------

def _frac(x):
    return (x - 1.0) / x


def _madd(total, mask, term):
    """Masked column add: exact (+0.0 off-mask), vmap/pad-safe."""
    return total + jnp.where(mask[None, :], term, jnp.zeros_like(term))


def _collective_bytes(static: StaticSpec, A: DeviceArrays,
                      si, so, kk, sif, sof, kkf, b_in):
    """Traced port of BatchedEvaluator._collective_bytes (mask-driven)."""
    fdt = sif.dtype
    train_mult = 2.0 if static.train else 1.0
    total = jnp.zeros_like(sif)
    batchf = A.batch.astype(fdt)
    rowsf = A.rows.astype(fdt)
    colsf = A.cols.astype(fdt)
    fmf = A.fm_width.astype(fdt)
    rows_eff = jnp.ones_like(rowsf) if static.decode else rowsf

    fm_shard = (batchf * rows_eff * fmf)[None, :] * BF16 / (b_in * kkf)

    total = _madd(total, A.m_tp, 2.0 * _frac(sof) * fm_shard * train_mult)

    tokens_shard = (batchf * rows_eff)[None, :] / (b_in * kkf)
    fanout = jnp.maximum(A.ep_topk, 1).astype(fdt)
    total = _madd(total, A.m_ep,
                  2.0 * tokens_shard * (fanout * fmf)[None, :] * BF16
                  * _frac(sof) * train_mult)

    total = _madd(total, A.m_vocab,
                  2.0 * _frac(sof) * fm_shard * train_mult)

    if static.decode:
        vhead = (colsf * batchf)[None, :] * BF16 / kkf * _frac(sof)
    else:
        # distributed softmax stats: constant in s_out, so the scalar
        # path's s_out > 1 guard must be kept explicitly
        vh = 2.0 * 8.0 * (batchf * rowsf)[None, :] / (b_in * kkf)
        vhead = jnp.where(so > 1, vh, jnp.zeros_like(vh))
    total = _madd(total, A.m_vhead, vhead)

    # sequence/context parallelism (s_in > 1): all terms carry the
    # (s_in-1)/s_in factor, vanishing at s_in = 1
    kvlf = A.kv_limit.astype(fdt)
    kv_div = jnp.where(A.kv_limit[None, :] > 0,
                       jnp.minimum(sof, kvlf[None, :]),
                       jnp.maximum(sof, 1.0))
    # latent attention combines its partials kv_lora_rank wide
    dh = jnp.where(A.m_latent, A.latent_dim.astype(fdt),
                   fmf / jnp.maximum(colsf, 1.0))
    total = _madd(total, A.internal,
                  (batchf[None, :] / kkf) * colsf[None, :]
                  / jnp.maximum(kv_div, 1.0) * ((dh + 2.0) * 4.0)[None, :]
                  * _frac(sif))
    # the latent cache is whole on every head fold
    ring_div = jnp.where(A.m_latent[None, :], jnp.ones_like(kv_div), kv_div)
    total = _madd(total, A.m_kv,
                  A.kv_bytes[None, :] / (ring_div * kkf) * _frac(sif)
                  * train_mult)
    total = _madd(total, A.m_carry,
                  A.carry_bytes[None, :] / kkf * _frac(sif) * train_mult)

    # data-parallel gradient all-reduce (per step, ring over k)
    if static.train:
        grad = A.weight_bytes / sof * 2.0 * static.grad_compression
        total = total + 2.0 * _frac(kkf) * grad
    return total


def _realizable(static: StaticSpec, A: DeviceArrays, si, so, kk):
    cap = A.val_cap                           # sentinel lut slot (-1)
    lut = A.val_lut
    ia = lut[jnp.minimum(si, cap)]
    ib = lut[jnp.minimum(so, cap)]
    ic = lut[jnp.minimum(kk, cap)]
    known = (ia >= 0) & (ib >= 0) & (ic >= 0)
    return known & A.real_table[jnp.maximum(ia, 0),
                                jnp.maximum(ib, 0),
                                jnp.maximum(ic, 0)]


def _eval_core(static: StaticSpec, A: DeviceArrays,
               si, so, kk, cb, single_partition: bool = False
               ) -> Dict[str, jax.Array]:
    """The batched array program on device; [N, n] fold arrays + [N, n-1]
    cut bitmask -> per-candidate results (a dict of jnp arrays).

    ``single_partition`` is a trace-time promise that every row of ``cb``
    is all-False (e.g. a brute-force chunk of the no-cut set): the
    partition machinery collapses to one max/sum over the node axis — the
    device analogue of the numpy engine's fast path."""
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
    b_in = jnp.where(A.internal[None, :], jnp.ones((), fdt), sif)
    compute_s = (A.flops / c) / (A.peak_flops * static.mxu_efficiency)

    w_per_chip = A.weight_bytes / sof
    act_per_chip = A.act_bytes / (b_in * kkf)
    inner_per_chip = A.inner_bytes / c

    # _state_sharding (KV sharding applies on attention-kind columns)
    kvlf = A.kv_limit.astype(fdt)
    kv_div_a = jnp.where(A.kv_limit[None, :] > 0,
                         jnp.minimum(sof, kvlf[None, :]), sof)
    # latent KV shards over (k, s_in) only: one vector per token
    state_div = jnp.where(A.m_attn[None, :],
                          kkf * jnp.maximum(kv_div_a, 1.0) * sif,
                          jnp.where(A.m_latent[None, :], kkf * sif,
                                    kkf * sof))
    state_repl = jnp.where(
        A.m_attn[None, :] & (A.kv_limit[None, :] > 0)
        & (so > A.kv_limit[None, :]),
        sof / kv_div_a, jnp.ones_like(sof))
    state_per_chip = A.state_bytes * state_repl / state_div

    train_mult = 3.0 if static.train else 1.0
    hbm = (act_per_chip + inner_per_chip) * train_mult
    if static.train:
        hbm = hbm + 2.0 * w_per_chip
    else:
        hbm = hbm + jnp.where(A.weight_stream, w_per_chip,
                              jnp.zeros_like(w_per_chip))
        hbm = hbm + state_per_chip
    memory_s = hbm / A.hbm_bw

    coll = _collective_bytes(static, A, si, so, kk, sif, sof, kkf, b_in)
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
            stash_div = stash_div * jnp.maximum(sof, 1.0)
        fm = A.node_d / BF16                   # batch*rows*fm_width, exact
        resident = resident + fm * BF16 / stash_div
        resident = _madd(resident, A.m_head,
                         3.0 * A.inner_bytes[None, :]
                         / (b_in * kkf * jnp.maximum(sof, 1.0)))
    else:
        rows = (jnp.ones_like(A.rows) if static.decode else A.rows).astype(fdt)
        resident = w_per_chip + state_per_chip \
            + 2.0 * (A.batch.astype(fdt) * rows * A.fm_width.astype(fdt)
                     * BF16)[None, :] / (b_in * kkf)

    node_time = jnp.maximum(jnp.maximum(compute_s, memory_s), collective_s)

    # ---------------- partition structure ---------------------------
    # (the numpy engine's no-cut fast path is a host shortcut; the general
    # segmented path below is exact for the no-cut case too)
    if n > 1:
        edge_valid = A.node_valid[:-1] & A.node_valid[1:]
        mism = ((b_in[:, :-1] != b_in[:, 1:]) | (kk[:, :-1] != kk[:, 1:])) \
            & edge_valid[None, :]
    else:
        mism = jnp.zeros((N, 0), bool)
    iota_n = jnp.arange(n, dtype=idt)
    # padded columns are neutral everywhere EXCEPT the streaming chip
    # count (their fold product is 1, not 0) — zero them explicitly there
    c_eff = jnp.where(A.node_valid[None, :], c, jnp.zeros_like(c))
    # resharding collectives at intra-partition layout changes (backends
    # without inter matching pay them), per edge
    if not static.inter_matching and n > 1:
        edge_t = jnp.where(~cb & mism, A.reshard_full[:-1] / A.ici_bw, 0.0)
    else:
        edge_t = jnp.zeros((N, max(n - 1, 0)), fdt)

    if single_partition:
        # fast path (trace-time): every candidate is one partition — no
        # segment reductions, no reconfiguration, no boundary staging
        pid = jnp.zeros((N, n), idt)
        nparts = jnp.ones((N,), idt)
        part_valid = iota_n[None, :] < 1
        t0 = node_time.max(axis=1) if static.exec_model == "streaming" \
            else node_time.sum(axis=1)
        if not static.inter_matching and n > 1:
            t0 = t0 + jnp.where(
                mism, A.reshard_full[:-1] / A.ici_bw, 0.0).sum(axis=1)
        t_part = jnp.zeros((N, n), t0.dtype).at[:, 0].set(t0)
        reconf = jnp.zeros((N,), fdt)
        sum_t = t0
    else:
        pid = jnp.concatenate(
            [jnp.zeros((N, 1), idt), jnp.cumsum(cb.astype(idt), axis=1)],
            axis=1)
        nparts = pid[:, -1] + 1
        part_valid = iota_n[None, :] < nparts[:, None]
        # Segmented reductions over the (tiny, static) node axis are dense:
        # a [N, n_src, n_part] partition one-hot turns seg-sum into a
        # batched matvec and seg-max into a masked max — XLA lowers both to
        # vector code, where a scatter-based segment_sum would serialise.
        onehot = pid[:, :, None] == iota_n[None, None, :]
        onehot_f = onehot.astype(fdt)

        def seg_sum(vals):
            return jnp.einsum("rj,rjp->rp", vals, onehot_f)

        def seg_max(vals):
            return jnp.max(jnp.where(onehot, vals[:, :, None], -jnp.inf),
                           axis=1)

        if static.use_pallas:
            from repro.core.accel.pallas_segred import segmented_reduce
            t_raw = segmented_reduce(node_time, pid,
                                     "max" if static.exec_model ==
                                     "streaming" else "sum",
                                     interpret=static.pallas_interpret)
            t_base = jnp.where(part_valid, t_raw, 0.0) \
                if static.exec_model == "streaming" else t_raw
        elif static.exec_model == "streaming":
            t_base = jnp.where(part_valid, seg_max(node_time), 0.0)
        else:
            t_base = seg_sum(node_time)

        t_part = t_base
        if not static.inter_matching and n > 1:
            reshard = jnp.einsum("rj,rjp->rp", edge_t, onehot_f[:, :-1, :])
            t_part = t_part + reshard
        t_part = jnp.where(part_valid, t_part, 0.0)

        # reconfiguration (Eq. 3): first configuration is pre-loaded
        w_part = seg_sum(w_per_chip)
        t_conf_part = A.reconf_fixed_s + w_part / A.dma_bw
        later = part_valid & (iota_n[None, :] >= 1)
        reconf = jnp.sum(jnp.where(later, t_conf_part, 0.0), axis=1)

        sum_t = t_part.sum(axis=1)
    latency = sum_t + reconf
    # objective configuration is per-problem DATA (lowering.py): both Eq. 3
    # and Eq. 4 are computed and a traced where selects — so one executable
    # serves any (objective, batch_amortisation) mix in a fleet bucket
    Bam = A.batch_amortisation
    thr_time = Bam * sum_t + reconf
    throughput = jnp.where(thr_time > 0,
                           Bam / jnp.where(thr_time > 0, thr_time, 1.0), 0.0)
    obj = jnp.where(A.obj_latency, latency, -throughput)

    # ---------------- constraints ----------------------------------
    bad = jnp.zeros(N, bool)
    # channel factor (Eq. 8) + cut legality + mesh realisability
    if n > 1:
        bad |= (cb & ~A.cut_allowed[None, :]).any(axis=1)
    bad |= (A.rows % si != 0).any(axis=1)
    bad |= (A.col_div % so != 0).any(axis=1)
    bad |= (A.batch % kk != 0).any(axis=1)
    if static.strict_kv:
        bad |= ((A.kv_limit > 0) & (so > A.kv_limit)).any(axis=1)
    bad |= ~_realizable(static, A, si, so, kk).all(axis=1)
    # intra matching (Eq. 9)
    if static.intra_matching:
        bad |= (A.elementwise & (si != so)).any(axis=1)
    # inter matching (Eq. 10), partition-local
    if static.inter_matching and n > 1:
        bad |= (~cb & mism).any(axis=1)
    # scan tying, partition-local (consecutive member pairs, padded with
    # (0, 0) self-pairs which can never differ)
    if static.scan_tying:
        a, b = A.pair_a, A.pair_b
        differ = (si[:, a] != si[:, b]) | (so[:, a] != so[:, b]) \
            | (kk[:, a] != kk[:, b])
        differ &= pid[:, a] == pid[:, b]
        bad |= differ.any(axis=1)
    # resource (Eq. 6) + streaming chip budget + bandwidth (Eq. 7)
    if single_partition:
        bad |= resident.sum(axis=1) > A.hbm_bytes
        if static.exec_model == "streaming":
            bad |= c_eff.sum(axis=1) > A.chips
        # single partition: no boundary staging, bandwidth never binds
    else:
        res_part = seg_sum(resident)
        multi = nparts > 1
        start = jnp.concatenate([jnp.ones((N, 1), bool), cb], axis=1)
        # the last partition ends at the last REAL node (padded columns
        # stage nothing)
        end = jnp.concatenate([cb, jnp.zeros((N, 1), bool)], axis=1) \
            | (iota_n == A.n_valid - 1)[None, :]
        d_io = seg_sum(A.node_d[None, :]
                       * (start.astype(fdt) + end.astype(fdt)))
        res_tot = res_part + jnp.where(multi[:, None],
                                       d_io / A.chips, 0.0)
        bad |= (part_valid & (res_tot > A.hbm_bytes)).any(axis=1)
        if static.exec_model == "streaming":
            chips_part = seg_sum(c_eff)
            bad |= (part_valid & (chips_part > A.chips)).any(axis=1)
        # bandwidth uses the pre-resharding partition interval, exactly
        # like constraints.check_bandwidth
        bw = A.hbm_bw * A.chips
        bw_bad = multi[:, None] & part_valid & (t_base > 0) \
            & (d_io / jnp.where(t_base > 0, t_base, 1.0) > bw)
        bad |= bw_bad.any(axis=1)

    return {
        "objective": obj, "feasible": ~bad, "latency": latency,
        "throughput": throughput, "part_times": t_part, "nparts": nparts,
        "reconf_time": reconf, "node_resident": resident,
        "node_times": node_time, "node_collective": coll,
        "edge_times": edge_t,
    }


@functools.partial(jax.jit, static_argnums=(0,))
def evaluate_batch_jax(static: StaticSpec, arrays: DeviceArrays,
                       si, so, kk, cb) -> Dict[str, jax.Array]:
    """Jitted standalone evaluate; cached per (StaticSpec, shapes)."""
    TRACE_COUNTS["eval_batch"] += 1
    return _eval_core(static, arrays, si, so, kk, cb)


# ----------------------------------------------------------------------
# host-facing wrapper
# ----------------------------------------------------------------------

class JaxEvaluator:
    """Device-resident counterpart of ``BatchedEvaluator``.

    Shares the host lowering (packing helpers, base designs, clamp/scope
    semantics) and evaluates through the jitted array program. Results come
    back as a numpy ``BatchResult`` so callers are engine-agnostic.

    ``pad_nodes`` pads the node axis (fleet bucketing); callers still pass
    unpadded [N, n] fold arrays — the wrapper pads candidates with neutral
    fold-1 columns and slices results back to the real node count.
    """

    def __init__(self, bev, *, use_pallas: bool = False,
                 pallas_interpret=None, pad_nodes=None, pad_pairs=None,
                 pad_vals=None, pad_lut=None):
        self.bev = bev
        self.static, self.arrays = lower_program(
            bev, use_pallas=use_pallas, pallas_interpret=pallas_interpret,
            pad_nodes=pad_nodes, pad_pairs=pad_pairs,
            pad_vals=pad_vals, pad_lut=pad_lut)
        self.n_pad = self.static.n_nodes

    @classmethod
    def from_problem(cls, problem, **kw) -> "JaxEvaluator":
        return cls(problem.batched(), **kw)

    # packing delegates to the host evaluator (same layout)
    def pack(self, designs):
        return self.bev.pack(designs)

    def unpack_row(self, si, so, kk, cb, row):
        return self.bev.unpack_row(si, so, kk, cb, row)

    def evaluate_batch(self, s_in, s_out, kern, cuts) -> BatchResult:
        si = np.asarray(s_in)
        so = np.asarray(s_out)
        kk = np.asarray(kern)
        cb = np.asarray(cuts, bool)
        N, n = si.shape
        if n != self.bev.n_nodes or so.shape != si.shape \
                or kk.shape != si.shape or cb.shape != (N, max(n - 1, 0)):
            raise ValueError(
                f"expected fold arrays [N, {self.bev.n_nodes}] and cut mask "
                f"[N, {self.bev.n_nodes - 1}]; got s_in {si.shape}, s_out "
                f"{so.shape}, kern {kk.shape}, cuts {cb.shape}")
        if self.n_pad > n:
            pad = ((0, 0), (0, self.n_pad - n))
            si = np.pad(si, pad, constant_values=1)
            so = np.pad(so, pad, constant_values=1)
            kk = np.pad(kk, pad, constant_values=1)
            cb = np.pad(cb, ((0, 0), (0, self.n_pad - 1 - cb.shape[1])),
                        constant_values=False)
        with _metrics.device_dispatch("eval_batch", batch=N):
            out = evaluate_batch_jax(self.static, self.arrays, si, so,
                                     kk, cb)
        with _trace.span("accel.d2h.eval_batch", batch=N):
            out = jax.device_get(out)
        return BatchResult(
            objective=np.asarray(out["objective"], np.float64),
            feasible=np.asarray(out["feasible"], bool),
            latency=np.asarray(out["latency"], np.float64),
            throughput=np.asarray(out["throughput"], np.float64),
            part_times=np.asarray(out["part_times"], np.float64)[:, :n],
            nparts=np.asarray(out["nparts"], np.int64),
            reconf_time=np.asarray(out["reconf_time"], np.float64),
            node_resident=np.asarray(out["node_resident"],
                                     np.float64)[:, :n],
            node_times=np.asarray(out["node_times"], np.float64)[:, :n],
            node_collective=np.asarray(out["node_collective"],
                                       np.float64)[:, :n],
        )
