"""Portfolio queue: trace-signature admission into in-flight fleet rounds.

Two halves:

``AdmissionQueue``
    A thread-safe, bounded FIFO of pending requests. ``submit()`` pushes
    (raising :class:`ServiceOverloaded` at capacity — bounded
    backpressure, never unbounded memory), the dispatcher drains either
    everything (``drain``) or only the requests matching a predicate
    (``drain_matching`` — the late-joiner poll of an in-flight lockstep
    round). Queue depth is exported as the ``service.queue.depth`` gauge.

``run_rule_based_lockstep``
    The streaming twin of ``fleet_rule_based``: every job's
    ``rule_based._algorithm2`` generator is advanced by one vmapped
    ``_fleet_rb_descend`` call per round, exactly like the fleet — but
    membership is DYNAMIC. A ``poll`` callback runs at every round
    boundary and may hand over newly arrived jobs from the queue: they
    join the next round as fresh lanes (late joiners). Jobs whose
    generator returns keep their lane as a ``cap=0`` no-op until the
    next membership change compacts the stack (early leavers) — the
    same inert-lane contract the fleet already uses for members with no
    pending request. Because the descent body, the pack/unpack lowering
    and the host merge loop are the fleet's own code shared verbatim
    (and padding is bit-neutral), every job's final design, objective,
    point count and history are bit-identical to a direct
    ``rule_based(problem, engine="jax")`` call — the service extends the
    differential ladder one layer up, and tests/test_service.py asserts
    it bitwise.

All jax imports are lazy: this module sits in the ``REPRO_NO_JAX``
import matrix (the server still serves host-engine requests without
jax); only ``run_rule_based_lockstep`` itself requires jax.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

__all__ = ["ServiceError", "ServiceOverloaded", "ServiceClosed",
           "DeadlineExceeded", "AdmissionQueue", "LockstepJob",
           "run_rule_based_lockstep"]


class ServiceError(RuntimeError):
    """Base class for mapping-service failures."""


class ServiceOverloaded(ServiceError):
    """The pending queue is full — resubmit later (bounded backpressure)."""


class ServiceClosed(ServiceError):
    """The server is shutting down (or closed) and accepts no new work."""


class DeadlineExceeded(ServiceError):
    """The request's deadline passed before its design was delivered."""


class AdmissionQueue:
    """Bounded thread-safe FIFO with predicate draining (see module doc)."""

    def __init__(self, maxsize: int = 256) -> None:
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self._lock = threading.Lock()
        self._nonempty = threading.Condition(self._lock)
        self._items: deque = deque()

    def _gauge(self) -> None:
        _metrics.gauge("service.queue.depth").set(len(self._items))

    def push(self, item) -> None:
        with self._nonempty:
            if len(self._items) >= self.maxsize:
                _metrics.counter("service.requests.rejected").inc()
                raise ServiceOverloaded(
                    f"pending queue is full ({self.maxsize} requests); "
                    f"retry later or raise max_pending")
            self._items.append(item)
            self._gauge()
            self._nonempty.notify_all()

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the queue is non-empty (or timeout); True if so."""
        with self._nonempty:
            if not self._items:
                self._nonempty.wait(timeout)
            return bool(self._items)

    def drain(self) -> List:
        with self._lock:
            out = list(self._items)
            self._items.clear()
            self._gauge()
        return out

    def drain_matching(self, pred: Callable) -> List:
        """Remove and return the pending items with ``pred(item)`` true,
        preserving FIFO order of the rest — the in-flight round's
        late-joiner poll."""
        with self._lock:
            out = [i for i in self._items if pred(i)]
            if out:
                self._items = deque(i for i in self._items
                                    if not pred(i))
                self._gauge()
        return out


# ----------------------------------------------------------------------
# dynamic-membership lockstep rounds (rule_based, jax engine)
# ----------------------------------------------------------------------

class LockstepJob:
    """One rule-based mapping job for the lockstep engine. ``tag`` is an
    opaque caller handle (the server keeps its request group there)."""

    __slots__ = ("problem", "multi_start", "tag")

    def __init__(self, problem, multi_start: bool = True, tag=None):
        self.problem = problem
        self.multi_start = multi_start
        self.tag = tag


class _Lane:
    __slots__ = ("job", "gen", "pending", "rb")

    def __init__(self, job, gen):
        self.job = job
        self.gen = gen
        self.pending = None          # (v, part) request or None when done
        self.rb = None               # DeviceRuleBased at the shared pads


def run_rule_based_lockstep(jobs: Sequence[LockstepJob],
                            poll: Optional[Callable[[], List[LockstepJob]]]
                            = None,
                            on_done: Optional[Callable] = None) -> List:
    """Advance many rule-based jobs in dynamic-membership lockstep rounds.

    All jobs (initial and polled) must share one trace-signature bucket
    (``fleet.bucket_key(problem)`` — the caller groups by it). ``poll``
    is invoked at every round boundary and returns newly admitted jobs
    (or ``[]``); ``on_done(job, result)`` fires the moment a job's
    generator returns, so early leavers resolve without waiting for the
    round loop to drain. Returns ``[(job, OptimResult), ...]`` in
    completion order.

    Padding grows monotonically (node/pair/menu axes tiered to
    ``fleet.NODE_TIER`` multiples, lane count to the next power of two,
    the sparse-attention terms on once a lane has ``dsa`` nodes)
    so late joiners usually ride an already-compiled executable; a
    joiner that genuinely needs bigger shapes restacks every lane and
    retraces once (counted in ``service.rounds.restacks``). Results are
    unaffected either way: padding is bit-neutral.
    """
    from repro.core.accel import require_jax
    require_jax()
    import jax
    import jax.numpy as jnp
    from repro.core.accel.fleet import (
        _fleet_rb_descend,
        _node_tier,
        _platform_pads,
        bucket_key,
    )
    from repro.core.accel.lowering import any_dsa
    from repro.core.accel.search_loops import (
        DeviceRuleBased,
        _pow2ceil,
        build_sa_tables,
    )
    from repro.core.optimizers.rule_based import _algorithm2

    pads = {"n": 0, "pairs": 0, "vals": 0, "lut": 0, "mm": 0, "dsa": 0}
    lanes: List[_Lane] = []
    done: List = []
    sig = [None]

    def finish(job, result) -> None:
        done.append((job, result))
        if on_done is not None:
            on_done(job, result)

    def build_rb(problem) -> DeviceRuleBased:
        tabs = build_sa_tables(problem, pad_nodes=pads["n"],
                               pad_val=pads["lut"] - 2)
        menus = tabs[0]
        if menus.shape[-1] < pads["mm"]:
            menus = np.pad(menus,
                           ((0, 0), (0, 0),
                            (0, pads["mm"] - menus.shape[-1])),
                           constant_values=1)
        return DeviceRuleBased(problem, pad_nodes=pads["n"],
                               pad_pairs=pads["pairs"],
                               pad_vals=pads["vals"], pad_lut=pads["lut"],
                               pad_dsa=bool(pads["dsa"]),
                               tables=(menus,) + tabs[1:])

    def admit(new_jobs: Sequence[LockstepJob]) -> bool:
        """Returns True when the lane stack must be rebuilt."""
        fresh: List[_Lane] = []
        for job in new_jobs:
            k = bucket_key(job.problem)
            if sig[0] is None:
                sig[0] = k
            elif k != sig[0]:
                raise ValueError(
                    "lockstep jobs must share one trace-signature bucket "
                    "(fleet.bucket_key); the caller groups requests "
                    "before admission")
            gen = _algorithm2(job.problem, None, job.multi_start)
            lane = _Lane(job, gen)
            try:
                lane.pending = next(gen)
            except StopIteration as stop:   # pragma: no cover (>= 1 part)
                finish(job, stop.value)
                continue
            fresh.append(lane)
        if not fresh:
            return False
        grew = False
        for lane in fresh:
            p = lane.job.problem
            va, lu = _platform_pads([p])
            wanted = (("n", _node_tier(len(p.graph.nodes))),
                      ("pairs", max(1, _node_tier(
                          len(p.batched().scan_pairs)))),
                      ("vals", _node_tier(va)),
                      ("lut", _node_tier(lu)),
                      # a sparse-attention lane turns its terms on for all
                      ("dsa", int(any_dsa([p]))))
            for key, v in wanted:
                if v > pads[key]:
                    pads[key] = v
                    grew = True
        # the menu radix only falls out of building the tables
        for lane in fresh:
            radix = build_sa_tables(
                lane.job.problem, pad_nodes=pads["n"],
                pad_val=pads["lut"] - 2)[0].shape[-1]
            mm = _node_tier(radix)
            if mm > pads["mm"]:
                pads["mm"] = mm
                grew = True
        if grew and any(ln.pending is not None for ln in lanes):
            _metrics.counter("service.rounds.restacks").inc()
        if grew:
            for lane in lanes:
                if lane.pending is not None:
                    lane.rb = build_rb(lane.job.problem)
        # compact early leavers out of the stack while we rebuild anyway
        lanes[:] = [ln for ln in lanes if ln.pending is not None]
        for lane in fresh:
            lane.rb = build_rb(lane.job.problem)
        lanes.extend(fresh)
        _metrics.counter("service.admissions").inc(len(fresh))
        return True

    def stack():
        P = len(lanes)
        P_pad = _pow2ceil(P)
        rbs = [ln.rb for ln in lanes] + [lanes[0].rb] * (P_pad - P)
        A_st = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                      *[r.A for r in rbs])
        return (P_pad, A_st,
                jnp.stack([r.menus for r in rbs]),
                jnp.stack([r.menu_sizes for r in rbs]),
                jnp.stack([r.clamp for r in rbs]),
                jnp.asarray(np.asarray([r.amort for r in rbs]),
                            rbs[0].A.flops.dtype))

    stacked = None
    if admit(list(jobs)):
        stacked = None
    rnd = 0
    while True:
        if poll is not None and admit(poll() or []):
            stacked = None
        if not any(ln.pending is not None for ln in lanes):
            break
        if stacked is None:
            stacked = stack()
        P_pad, A_st, menus_st, sizes_st, clamp_st, amort = stacked
        rb0 = lanes[0].rb
        static, gran = rb0.static, rb0.gran
        assert all(ln.rb.static == static and ln.rb.gran == gran
                   for ln in lanes if ln.pending is not None), \
            "lockstep lanes must share a StaticSpec"
        idt_np = np.int64 if str(rb0.A.batch.dtype) == "int64" else np.int32
        n_pad = static.n_nodes
        E = max(n_pad - 1, 0)
        si = np.ones((P_pad, n_pad), idt_np)
        so = np.ones((P_pad, n_pad), idt_np)
        kk = np.ones((P_pad, n_pad), idt_np)
        cb = np.zeros((P_pad, E), bool)
        pm = np.zeros((P_pad, n_pad), bool)
        pidx = np.zeros(P_pad, idt_np)
        cap = np.zeros(P_pad, idt_np)       # 0 => inert no-op lane
        active = 0
        for li, lane in enumerate(lanes):
            if lane.pending is None:
                continue                    # early leaver: cap stays 0
            v, part = lane.pending
            (si[li], so[li], kk[li], cb[li], pm[li], pidx[li],
             cap[li]) = lane.rb.pack_request(v, part)
            active += 1
        _metrics.gauge("service.lanes").set(active)
        with _trace.span("service.round", round=rnd, lanes=active,
                         lanes_padded=P_pad):
            with _metrics.device_dispatch("fleet_rb_descend",
                                          bucket="service", round=rnd):
                out = _fleet_rb_descend(
                    static, gran, A_st, menus_st, sizes_st, clamp_st,
                    jnp.asarray(si), jnp.asarray(so), jnp.asarray(kk),
                    jnp.asarray(cb), jnp.asarray(pm), jnp.asarray(pidx),
                    amort, jnp.asarray(cap))
            with _trace.span("service.d2h.round"):
                o_si, o_so, o_kk, pts = (np.asarray(x) for x in out)
        _metrics.counter("service.rounds").inc()
        rnd += 1
        for li, lane in enumerate(lanes):
            if lane.pending is None:
                continue
            v, part = lane.pending
            resp = lane.rb.unpack(v, o_si[li], o_so[li], o_kk[li],
                                  pts[li])
            try:
                lane.pending = lane.gen.send(resp)
            except StopIteration as stop:
                lane.pending = None
                finish(lane.job, stop.value)
    return done
