"""Lowering: BatchedEvaluator flat numpy arrays -> JAX device constants.

The host lowering (``core/batched_eval.py``) already flattens an HDGraph +
Platform + ModelOptions into per-node numpy arrays; this module converts that
result into the two halves a jitted program needs:

  ``StaticSpec``    an immutable, hashable bundle of everything that shapes
                    the traced program: mode/backend flags, ModelOptions,
                    and the (padded) node count. Since PR 3 the spec
                    carries NO per-architecture structure, since PR 4 NO
                    platform identity, and since PR 5 NO objective
                    configuration either — kind columns, scan groups,
                    tying pairs, resource limits, bandwidth scalars, the
                    fold-realisability cube, the Eq. 5 objective selector
                    and the Eq. 4 batch-amortisation factor all live in
                    ``DeviceArrays`` as data — so two different graphs on
                    two different *platforms* optimising two different
                    *objectives* with the same mode/backend flags and
                    padded shapes share ONE spec and hence one XLA
                    executable, and the fleet engine (``fleet.py``) can
                    ``vmap`` the program across a stacked
                    (model, platform, objective) problem axis.
  ``DeviceArrays``  a NamedTuple pytree of ``jnp`` arrays: per-node
                    workload quantities, kind masks, scan-tying pairs,
                    validity masks, the per-problem platform scalars
                    (``peak_flops`` .. ``chips``) and the
                    mesh-realisability lookup tables.

Padding: ``lower_program(..., pad_nodes=N)`` pads every per-node array to N
columns with *neutral* nodes (zero work, fold menus pinned to 1, no cuts
allowed into them) and records the real node count in ``node_valid`` /
``n_valid``. Padded evaluation is bit-identical to unpadded evaluation —
each padded column contributes exactly ``+0.0`` / ``max(..., 0.0)`` /
``False`` to every reduction — which is what lets the fleet engine stack
differently-sized graphs into one program (tests assert the bitwise
agreement). ``pad_vals`` / ``pad_lut`` pad the realisability cube and the
value->menu-index lut the same way (unknown values are infeasible either
way), so problems on platforms with different fold menus can also share
one executable.

Precision: device arrays are float32/int32 unless jax x64 is enabled
(``jax.config.update("jax_enable_x64", True)``), in which case the lowering
emits float64/int64 and the engine agrees with the scalar reference at 1e-9
(see tests/test_accel_engine.py).
"""
from __future__ import annotations

import dataclasses
import hashlib
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import numpy as np

from repro.core.accel import EngineUnavailable, require_jax
from repro.obs import trace as _trace

#: realisability tables are built by calling ``platform.folds_realizable``
#: over the fold-value cube; above this menu size the cube is too expensive
#: to enumerate scalar-by-scalar for platforms without a product rule.
MAX_TABLE_VALUES = 64


@dataclass(frozen=True)
class StaticSpec:
    """Hashable trace-shaping configuration for the jitted array program.

    Deliberately architecture-free AND platform-free: everything that
    differs between two graphs, or between two target platforms, is array
    *data* (``DeviceArrays``), not trace structure. Only mode/backend
    rule flags, ModelOptions and the padded node count remain — the things
    that genuinely change which operations the traced program performs.
    Since PR 5 the per-problem objective (``latency`` vs ``throughput``)
    and ``batch_amortisation`` are data too (``DeviceArrays.obj_latency``
    / ``.batch_amortisation``): Eq. 5 selects the objective with a traced
    ``where`` over both computed branches, so a mixed-objective fleet
    bucket shares one executable. ``n_nodes`` is the PADDED node count
    when the lowering was padded.
    """

    n_nodes: int
    mode: str                       # train | prefill | decode
    exec_model: str                 # streaming | spmd
    strict_kv: bool
    intra_matching: bool
    inter_matching: bool
    scan_tying: bool
    # ModelOptions
    zero1: bool
    seq_parallel_stash: bool
    grad_compression: float
    mxu_efficiency: float
    overlap_collectives: float
    use_pallas: bool = False        # Pallas segmented reduction for T(P_i)
    pallas_interpret: bool = False  # interpret-mode fallback (CPU)

    @property
    def train(self) -> bool:
        return self.mode == "train"

    @property
    def decode(self) -> bool:
        return self.mode == "decode"


class DeviceArrays(NamedTuple):
    """Per-node device constants (a pytree; all leaves are jnp arrays).

    The fleet engine stacks several problems' ``DeviceArrays`` along a new
    leading axis and ``vmap``s the evaluation over it, so every
    per-problem quantity — including the kind masks and the scan-tying
    pair lists — must be a leaf here, never static trace structure.
    """

    flops: "jax.Array"
    weight_bytes: "jax.Array"
    act_bytes: "jax.Array"
    inner_bytes: "jax.Array"
    state_bytes: "jax.Array"
    kv_bytes: "jax.Array"
    carry_bytes: "jax.Array"
    node_d: "jax.Array"
    reshard_full: "jax.Array"
    batch: "jax.Array"
    rows: "jax.Array"
    cols: "jax.Array"
    fm_width: "jax.Array"
    col_div: "jax.Array"
    kv_limit: "jax.Array"
    latent_dim: "jax.Array"
    ep_topk: "jax.Array"
    scan_group: "jax.Array"
    internal: "jax.Array"
    elementwise: "jax.Array"
    weight_stream: "jax.Array"
    cut_allowed: "jax.Array"
    real_table: "jax.Array"         # [nv, nv, nv] bool over the fold menu
    val_lut: "jax.Array"            # fold value -> menu index (-1 unknown)
    val_cap: "jax.Array"            # scalar: realisability lut sentinel slot
    # platform scalars — per-problem DATA, so one executable serves any
    # platform and the fleet can stack (model, platform) pairs
    peak_flops: "jax.Array"         # scalar, float
    hbm_bw: "jax.Array"
    hbm_bytes: "jax.Array"
    ici_bw: "jax.Array"
    dma_bw: "jax.Array"
    reconf_fixed_s: "jax.Array"
    chips: "jax.Array"              # scalar, float (exact: chips <= 2**24)
    # per-problem objective configuration — DATA since PR 5, so a fleet
    # bucket may mix objectives and amortisation factors without splitting
    # the cached executable (Eq. 5 selects via a traced where, Eq. 4's B
    # is a runtime scalar)
    obj_latency: "jax.Array"        # scalar bool: True => Eq. 3 latency
    batch_amortisation: "jax.Array"  # scalar, float (B in Eq. 4; exact)
    # kind-specific column masks (see batched_eval._lower's index sets)
    m_attn: "jax.Array"
    m_head: "jax.Array"
    m_tp: "jax.Array"
    m_ep: "jax.Array"
    m_vocab: "jax.Array"
    m_vhead: "jax.Array"
    m_kv: "jax.Array"
    m_carry: "jax.Array"
    m_latent: "jax.Array"
    # scan-tying consecutive member pairs, padded with (0, 0) self-pairs
    pair_a: "jax.Array"             # [n_pairs_pad]
    pair_b: "jax.Array"
    # padding bookkeeping
    node_valid: "jax.Array"         # [n] bool; False on padded columns
    n_valid: "jax.Array"            # scalar: count of real nodes


def _realizability_table(bev) -> Tuple[np.ndarray, np.ndarray, int]:
    """(table, lut, cap) — reuse the host evaluator's table, or build one.

    ``batched_eval`` builds the cube only for menus of <= 24 values; the jax
    engine needs it always (the memoised unique-triple fallback is a host
    loop). AbstractPlatform realisability is a pure product rule, so its
    cube vectorises at any size; generic platforms are enumerated up to
    ``MAX_TABLE_VALUES`` menu entries.
    """
    if getattr(bev, "_real_table", None) is not None:
        return bev._real_table, bev._val_lut, bev._val_max + 1

    plat = bev.platform
    vals = np.asarray(plat.fold_values(), np.int64)
    nv = len(vals)
    # duck-typed product rule (AbstractPlatform): realisable iff the product
    # of folds fits the mesh — vectorise instead of nv^3 scalar calls.
    from repro.core.platform import AbstractPlatform
    if isinstance(plat, AbstractPlatform):
        prod = vals[:, None, None] * vals[None, :, None] * vals[None, None, :]
        table = prod <= plat.chips
    elif nv <= MAX_TABLE_VALUES:
        table = np.zeros((nv, nv, nv), bool)
        for a, fa in enumerate(vals):
            for b, fb in enumerate(vals):
                for d, fd in enumerate(vals):
                    table[a, b, d] = plat.folds_realizable((fa, fb, fd))
    else:
        raise EngineUnavailable(
            f"platform {plat.name!r} has {nv} fold values; the jax engine "
            f"needs a dense realisability table (<= {MAX_TABLE_VALUES} "
            f"values) or an AbstractPlatform product rule. Use "
            f"engine='numpy' for this platform.")
    val_max = int(vals[-1])
    lut = np.full(val_max + 2, -1, np.int64)
    lut[vals] = np.arange(nv)
    return table, lut, val_max + 1


def _pad1(a: np.ndarray, n_pad: int, fill) -> np.ndarray:
    """Pad a per-node (or per-edge) 1-D array to ``n_pad`` with ``fill``."""
    if len(a) >= n_pad:
        return a
    out = np.full(n_pad, fill, a.dtype)
    out[:len(a)] = a
    return out


def _mask(index_set, n: int, n_pad: int) -> np.ndarray:
    m = np.zeros(n_pad, bool)
    m[np.asarray(index_set, np.int64)] = True
    return m


@_trace.traced("accel.build_static_spec")
def build_static_spec(bev, *, use_pallas: bool = False,
                      pallas_interpret: bool = False,
                      pad_nodes: Optional[int] = None) -> StaticSpec:
    """Pure-host construction of the trace-shaping spec (no jax needed).

    This is the static-analysis hook: ``repro.analysis.recompile_lint``
    builds specs for a whole (arch, platform, objective) example grid —
    in the no-jax CI lane too — and flags any field whose value varies
    across the grid, i.e. data that should have been a ``DeviceArrays``
    leaf. ``lower_program`` routes through here so the linted spec and
    the spec that actually keys the XLA executable cache can never drift.
    Unlike ``lower_program``, ``pallas_interpret`` has no backend-probing
    default — callers without jax must pick explicitly.
    """
    n = bev.n_nodes
    np_ = n if pad_nodes is None else int(pad_nodes)
    if np_ < n:
        raise ValueError(f"pad_nodes={np_} < graph node count {n}")
    opts = bev.opts
    return StaticSpec(
        n_nodes=np_,
        mode=bev.mode,
        exec_model=bev.exec_model,
        strict_kv=bev.strict_kv,
        intra_matching=bev.intra_matching,
        inter_matching=bev.inter_matching,
        scan_tying=bev.scan_tying,
        zero1=opts.zero1,
        seq_parallel_stash=opts.seq_parallel_stash,
        grad_compression=opts.grad_compression,
        mxu_efficiency=opts.mxu_efficiency,
        overlap_collectives=opts.overlap_collectives,
        use_pallas=use_pallas,
        pallas_interpret=pallas_interpret,
    )


#: BatchedEvaluator arrays covered by ``problem_fingerprint``, in
#: ``DeviceArrays`` field order — exactly the per-node/per-edge content
#: ``lower_program`` ships to the device. Extending ``DeviceArrays`` with
#: a new lowered array means extending this tuple too (the fingerprint
#: must keep covering everything that shapes engine results).
FINGERPRINT_ARRAYS: Tuple[str, ...] = (
    "flops", "weight_bytes", "act_bytes", "inner_bytes", "state_bytes",
    "kv_bytes", "carry_bytes", "node_d", "reshard_full", "batch", "rows",
    "cols", "fm_width", "col_div", "kv_limit", "latent_dim", "ep_topk",
    "scan_group", "internal", "elementwise", "weight_stream", "cut_allowed",
)

#: kind index sets covered by ``problem_fingerprint`` (the
#: ``DeviceArrays.m_*`` mask sources).
FINGERPRINT_INDEX_SETS: Tuple[str, ...] = (
    "i_attn", "i_head", "i_tp", "i_ep", "i_vocab", "i_vhead", "i_kv",
    "i_carry", "i_latent",
)


@_trace.traced("accel.problem_fingerprint")
def problem_fingerprint(problem) -> str:
    """Canonical content hash of a Problem's lowered program (no jax).

    Routes through ``build_static_spec`` — the same keying path that
    shapes the XLA executable cache and that ``recompile_lint`` audits —
    and then hashes every array ``lower_program`` would ship to the
    device: the per-node workload quantities, kind index sets, scan
    pairs, platform scalar vector, fold-realisability cube/lut, plus the
    Eq. 5 objective flag and Eq. 4 amortisation factor. Two problems
    with equal fingerprints lower to bit-identical device programs (at
    any shared padding — padding is excluded on purpose: it is
    bit-neutral by the lowering contract, so it cannot change results),
    and therefore every deterministic engine returns identical designs,
    objectives and histories for them. This is the keying contract the
    service cache (``repro/service/cache.py``) and the
    ``optimise_portfolio`` duplicate-coalescing fix rely on
    (docs/service.md documents it).

    Accepts a ``Problem`` (lowers via its cached ``batched()``) or a
    ``BatchedEvaluator`` directly. Pure host, jax-free.
    """
    bev = problem.batched() if hasattr(problem, "batched") else problem
    # engine knobs (use_pallas / interpret mode) change the kernel route,
    # not the computed design — pin them so the fingerprint is a problem
    # identity, not an engine configuration
    static = build_static_spec(bev, use_pallas=False,
                               pallas_interpret=False)
    h = hashlib.sha256(b"repro.problem_fingerprint.v1")
    h.update(repr(dataclasses.astuple(static)).encode())

    def feed(name: str, a: np.ndarray) -> None:
        a = np.ascontiguousarray(a)
        h.update(f"|{name}:{a.dtype.str}:{a.shape}|".encode())
        h.update(a.tobytes())

    for name in FINGERPRINT_ARRAYS:
        feed(name, np.asarray(getattr(bev, name)))
    for name in FINGERPRINT_INDEX_SETS:
        feed(name, np.asarray(sorted(getattr(bev, name)), np.int64))
    feed("scan_pairs", np.asarray(bev.scan_pairs, np.int64))
    feed("platform_scalars", np.asarray(bev.platform_scalars(),
                                        np.float64))
    try:
        table, lut, cap = _realizability_table(bev)
        feed("real_table", table.astype(np.uint8))
        feed("val_lut", np.asarray(lut, np.int64))
        h.update(f"|cap:{int(cap)}|".encode())
    except EngineUnavailable:
        # menus too large for a dense cube (numpy-engine-only platforms):
        # the fold menu plus the platform name pins the candidate space —
        # a false MISS is possible across renamed-but-identical platforms,
        # a false HIT is not
        feed("fold_values", np.asarray(bev.platform.fold_values(),
                                       np.int64))
        h.update(f"|platform:{bev.platform.name}|".encode())
    h.update(f"|objective:{bev.objective}"
             f"|amort:{float(bev.batch_amortisation)!r}|".encode())
    return h.hexdigest()


@_trace.traced("accel.lower_program")
def lower_program(bev, *, use_pallas: bool = False,
                  pallas_interpret: bool | None = None,
                  pad_nodes: Optional[int] = None,
                  pad_pairs: Optional[int] = None,
                  pad_vals: Optional[int] = None,
                  pad_lut: Optional[int] = None
                  ) -> Tuple[StaticSpec, DeviceArrays]:
    """Lower a host ``BatchedEvaluator`` onto the default jax device.

    ``use_pallas`` routes the partition-time segmented reduction through the
    Pallas kernel (the TPU hot path); ``pallas_interpret`` forces interpret
    mode (defaults to True off-TPU so the kernel stays runnable on CPU).
    ``pad_nodes``/``pad_pairs`` pad the node axis / scan-pair list so
    problems of different sizes can share one StaticSpec (fleet sweeps);
    padded columns are neutral and provably cannot change any result.
    ``pad_vals``/``pad_lut`` pad the fold-realisability cube and the
    value->index lut the same way (False / -1 fill: a padded slot is
    "unknown value" and unknown values were already infeasible), so
    problems on *different platforms* — whose fold menus differ in size —
    can also share one StaticSpec and hence one executable.
    """
    jax = require_jax()
    import jax.numpy as jnp

    x64 = jax.config.jax_enable_x64
    fdt = jnp.float64 if x64 else jnp.float32
    idt = jnp.int64 if x64 else jnp.int32

    table, lut, cap = _realizability_table(bev)
    nv = table.shape[0]
    pv = nv if pad_vals is None else int(pad_vals)
    if pv < nv:
        raise ValueError(f"pad_vals={pv} < fold menu size {nv}")
    if pv > nv:
        t2 = np.zeros((pv, pv, pv), bool)
        t2[:nv, :nv, :nv] = table
        table = t2
    pl = len(lut) if pad_lut is None else int(pad_lut)
    if pl < len(lut):
        raise ValueError(f"pad_lut={pl} < lut length {len(lut)}")
    lut = _pad1(lut, pl, -1)
    if pallas_interpret is None:
        pallas_interpret = jax.default_backend() != "tpu"

    static = build_static_spec(bev, use_pallas=use_pallas,
                               pallas_interpret=pallas_interpret,
                               pad_nodes=pad_nodes)
    n = bev.n_nodes
    np_ = static.n_nodes
    # the platform scalar vector (batched_eval.PLATFORM_SCALAR_FIELDS
    # order) becomes per-problem device data — never trace structure
    pf, hbw, hby, ibw, dbw, rfs, chips = bev.platform_scalars()

    # scan-tying pairs padded with (0, 0): a self-pair can never "differ"
    pairs = bev.scan_pairs
    pp = max(pairs.shape[0], 1) if pad_pairs is None else int(pad_pairs)
    if pp < pairs.shape[0]:
        raise ValueError(f"pad_pairs={pp} < pair count {pairs.shape[0]}")
    pair_a = np.zeros(pp, np.int64)
    pair_b = np.zeros(pp, np.int64)
    pair_a[:pairs.shape[0]] = pairs[:, 0]
    pair_b[:pairs.shape[0]] = pairs[:, 1]

    node_valid = np.zeros(np_, bool)
    node_valid[:n] = True

    ef = lambda a, fill: jnp.asarray(_pad1(np.asarray(a, np.float64),
                                           np_, fill), fdt)
    ei = lambda a, fill: jnp.asarray(_pad1(np.asarray(a, np.int64),
                                           np_, fill), idt)
    eb = lambda a: jnp.asarray(_pad1(np.asarray(a, bool), np_, False))
    km = lambda ix: jnp.asarray(_mask(ix, n, np_))

    arrays = DeviceArrays(
        flops=ef(bev.flops, 0.0),
        weight_bytes=ef(bev.weight_bytes, 0.0),
        act_bytes=ef(bev.act_bytes, 0.0),
        inner_bytes=ef(bev.inner_bytes, 0.0),
        state_bytes=ef(bev.state_bytes, 0.0),
        kv_bytes=ef(bev.kv_bytes, 0.0),
        carry_bytes=ef(bev.carry_bytes, 0.0),
        node_d=ef(bev.node_d, 0.0),
        reshard_full=ef(bev.reshard_full, 0.0),
        batch=ei(bev.batch, 1),
        rows=ei(bev.rows, 1),
        cols=ei(bev.cols, 1),
        fm_width=ei(bev.fm_width, 0),
        col_div=ei(bev.col_div, 1),
        kv_limit=ei(bev.kv_limit, 0),
        latent_dim=ei(bev.latent_dim, 0),
        ep_topk=ei(bev.ep_topk, 0),
        scan_group=ei(bev.scan_group, -1),
        internal=eb(bev.internal),
        elementwise=eb(bev.elementwise),
        weight_stream=eb(bev.weight_stream),
        cut_allowed=jnp.asarray(_pad1(np.asarray(bev.cut_allowed, bool),
                                      max(np_ - 1, 0), False)),
        real_table=jnp.asarray(table),
        val_lut=jnp.asarray(lut, idt),
        val_cap=jnp.asarray(cap, idt),
        peak_flops=jnp.asarray(pf, fdt),
        hbm_bw=jnp.asarray(hbw, fdt),
        hbm_bytes=jnp.asarray(hby, fdt),
        ici_bw=jnp.asarray(ibw, fdt),
        dma_bw=jnp.asarray(dbw, fdt),
        reconf_fixed_s=jnp.asarray(rfs, fdt),
        chips=jnp.asarray(chips, fdt),
        obj_latency=jnp.asarray(bev.objective == "latency"),
        batch_amortisation=jnp.asarray(float(bev.batch_amortisation), fdt),
        m_attn=km(bev.i_attn),
        m_head=km(bev.i_head),
        m_tp=km(bev.i_tp),
        m_ep=km(bev.i_ep),
        m_vocab=km(bev.i_vocab),
        m_vhead=km(bev.i_vhead),
        m_kv=km(bev.i_kv),
        m_carry=km(bev.i_carry),
        m_latent=km(bev.i_latent),
        pair_a=jnp.asarray(pair_a, idt),
        pair_b=jnp.asarray(pair_b, idt),
        node_valid=jnp.asarray(node_valid),
        n_valid=jnp.asarray(n, idt),
    )
    return static, arrays
