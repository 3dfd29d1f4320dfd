"""The jax engine's evaluator: the array program jitted onto the device.

``_eval_core`` is ``core/array_program.eval_core`` run on ``jax.numpy``,
with segmented partition reductions as dense one-hot contractions
(``_JaxSegments``). The numpy engine (``core/batched_eval.py``) runs the
same program on the host record; only the reductions differ. Every
kind-specific term is a ``jnp.where`` over a mask stored in
``DeviceArrays``, and because the mask is *data*, the same traced program
serves any architecture: the fleet engine (``fleet.py``) vmaps this
function across a stacked problem axis, and padded columns
(``DeviceArrays.node_valid``) contribute exactly zero to every reduction.
Platform scalars (resource limits, bandwidths, ``chips``, the
realisability lut sentinel) are likewise read from ``DeviceArrays`` —
scalar operands, so the program is bitwise independent of *which*
platform supplied them: one executable serves any platform, and vmapping
over stacked per-problem scalar rows serves a heterogeneous
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
from repro.core.array_program import eval_core
from repro.core.batched_eval import BatchResult


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

class _JaxSegments:
    """The device engine's reductions. Segmented reductions over the (tiny,
    static) node axis are dense: a [N, n_src, n_part] partition one-hot
    turns seg-sum into a batched matvec and seg-max into a masked max —
    XLA lowers both to vector code, where a scatter-based segment_sum
    would serialise."""

    @staticmethod
    def reducers(pid, iota_n, fdt):
        onehot = pid[:, :, None] == iota_n[None, None, :]
        onehot_f = onehot.astype(fdt)

        def seg_sum(vals):
            return jnp.einsum("rj,rjp->rp", vals, onehot_f)

        def seg_max(vals):
            return jnp.max(jnp.where(onehot, vals[:, :, None], -jnp.inf),
                           axis=1)

        def edge_sum(vals):
            return jnp.einsum("rj,rjp->rp", vals, onehot_f[:, :-1, :])

        return seg_sum, seg_max, edge_sum

    @staticmethod
    def row_sum(x):
        return x.sum(axis=1)

    @staticmethod
    def first_column(t0, n):
        return jnp.zeros((t0.shape[0], n), t0.dtype).at[:, 0].set(t0)


def _eval_core(static: StaticSpec, A: DeviceArrays,
               si, so, kk, cb, single_partition: bool = False
               ) -> Dict[str, jax.Array]:
    """``array_program.eval_core`` on jnp: the body every jitted search
    program traces."""
    return eval_core(static, A, si, so, kk, cb, single_partition,
                     xp=jnp, seg=_JaxSegments)


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

    def __init__(self, bev, *, pad_nodes=None, pad_pairs=None,
                 pad_vals=None, pad_lut=None):
        self.bev = bev
        self.static, self.arrays = lower_program(
            bev, pad_nodes=pad_nodes, pad_pairs=pad_pairs,
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
