"""Lowering: a BatchedEvaluator's node arrays -> the array program's inputs.

The array program (``core/array_program.py``) reads two things:

  ``StaticSpec``    an immutable, hashable bundle of everything that shapes
                    the traced program: mode/backend flags, ModelOptions,
                    and the (padded) node count. The spec carries NO
                    per-architecture structure, NO platform identity and
                    NO objective configuration — kind columns, scan groups,
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
  ``DeviceArrays``  a NamedTuple record of per-node workload quantities,
                    kind masks, scan-tying pairs, validity masks, the
                    per-problem platform scalars (``peak_flops`` ..
                    ``chips``) and the mesh-realisability lookup tables.

``host_arrays`` builds the record once per problem as float64/int64 numpy
arrays, unpadded; the numpy engine evaluates on it directly.
``lower_program`` is that record padded and moved to the jax device at the
device dtype — nothing is built twice.

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

#: realisability cubes are built by calling ``platform.folds_realizable``
#: over the fold-value cube; above this menu size the cube is too expensive
#: to enumerate scalar-by-scalar for platforms without a product rule.
MAX_TABLE_VALUES = 64


@dataclass(frozen=True)
class StaticSpec:
    """Hashable trace-shaping configuration for the array program.

    Deliberately architecture-free AND platform-free: everything that
    differs between two graphs, or between two target platforms, is array
    *data* (``DeviceArrays``), not trace structure. Only mode/backend
    rule flags, ModelOptions and the padded node count remain — the things
    that genuinely change which operations the traced program performs.
    The per-problem objective (``latency`` vs ``throughput``) and
    ``batch_amortisation`` are data too (``DeviceArrays.obj_latency``
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

    @property
    def train(self) -> bool:
        return self.mode == "train"

    @property
    def decode(self) -> bool:
        return self.mode == "decode"


class DeviceArrays(NamedTuple):
    """Per-problem inputs of the array program: numpy arrays on the host
    (``host_arrays``), jnp arrays on the device (``lower_program``).

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
    # per-problem objective configuration — DATA, so a fleet bucket may
    # mix objectives and amortisation factors without splitting the
    # cached executable (Eq. 5 selects via a traced where, Eq. 4's B is a
    # runtime scalar)
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
    # scan-tying consecutive member pairs; the device record pads them
    # with (0, 0) self-pairs
    pair_a: "jax.Array"             # [n_pairs_pad]
    pair_b: "jax.Array"
    # padding bookkeeping
    node_valid: "jax.Array"         # [n] bool; False on padded columns
    n_valid: "jax.Array"            # scalar: count of real nodes


def realizability_table(platform
                        ) -> Optional[Tuple[np.ndarray, np.ndarray, int]]:
    """(table, lut, cap) over the platform's fold menu, or None.

    ``table[a, b, c]`` says whether the fold triple (menu[a], menu[b],
    menu[c]) is mesh-realisable; ``lut`` maps a fold value to its menu
    index (-1: not a menu value, so never realisable) and ``cap`` is the
    lut's sentinel slot for values past the menu. AbstractPlatform
    realisability is a pure product rule, so its cube vectorises at any
    size; other platforms are enumerated up to ``MAX_TABLE_VALUES`` menu
    entries. Past that there is no cube (None): the jax engine refuses
    the platform and the numpy engine tests triples one by one.
    """
    vals = np.asarray(platform.fold_values(), np.int64)
    nv = len(vals)
    from repro.core.platform import AbstractPlatform
    if isinstance(platform, AbstractPlatform):
        # a*b*c <= chips  <=>  a*b <= chips // c, for positive folds
        ab = vals[:, None] * vals[None, :]
        table = ab[:, :, None] <= (platform.chips // vals)[None, None, :]
    elif nv <= MAX_TABLE_VALUES:
        table = np.zeros((nv, nv, nv), bool)
        for a, fa in enumerate(vals):
            for b, fb in enumerate(vals):
                for d, fd in enumerate(vals):
                    table[a, b, d] = platform.folds_realizable((fa, fb, fd))
    else:
        return None
    val_max = int(vals[-1])
    lut = np.full(val_max + 2, -1, np.int64)
    lut[vals] = np.arange(nv)
    return table, lut, val_max + 1


def _mask(index_set, n: int) -> np.ndarray:
    m = np.zeros(n, bool)
    m[np.asarray(index_set, np.int64)] = True
    return m


def host_arrays(bev) -> DeviceArrays:
    """The array program's record for a BatchedEvaluator, on the host:
    float64/int64/bool numpy arrays, unpadded. Derived from the
    evaluator's attributes as ``_lower`` left them. Without a
    realisability cube (``realizability_table`` is None) the three table
    fields are None."""
    n = bev.n_nodes
    f = lambda a: np.asarray(a, np.float64)
    i = lambda a: np.asarray(a, np.int64)
    b = lambda a: np.asarray(a, bool)
    table, lut, cap = realizability_table(bev.platform) or (None,) * 3
    pf, hbw, hby, ibw, dbw, rfs, chips = bev.platform_scalars()
    return DeviceArrays(
        flops=f(bev.flops), weight_bytes=f(bev.weight_bytes),
        act_bytes=f(bev.act_bytes), inner_bytes=f(bev.inner_bytes),
        state_bytes=f(bev.state_bytes), kv_bytes=f(bev.kv_bytes),
        carry_bytes=f(bev.carry_bytes), node_d=f(bev.node_d),
        reshard_full=f(bev.reshard_full),
        batch=i(bev.batch), rows=i(bev.rows), cols=i(bev.cols),
        fm_width=i(bev.fm_width), col_div=i(bev.col_div),
        kv_limit=i(bev.kv_limit), latent_dim=i(bev.latent_dim),
        ep_topk=i(bev.ep_topk), scan_group=i(bev.scan_group),
        internal=b(bev.internal), elementwise=b(bev.elementwise),
        weight_stream=b(bev.weight_stream), cut_allowed=b(bev.cut_allowed),
        real_table=table, val_lut=lut,
        val_cap=None if cap is None else np.asarray(cap, np.int64),
        peak_flops=pf, hbm_bw=hbw, hbm_bytes=hby, ici_bw=ibw, dma_bw=dbw,
        reconf_fixed_s=rfs, chips=chips,
        obj_latency=np.asarray(bev.objective == "latency"),
        batch_amortisation=np.asarray(float(bev.batch_amortisation)),
        m_attn=_mask(bev.i_attn, n), m_head=_mask(bev.i_head, n),
        m_tp=_mask(bev.i_tp, n), m_ep=_mask(bev.i_ep, n),
        m_vocab=_mask(bev.i_vocab, n), m_vhead=_mask(bev.i_vhead, n),
        m_kv=_mask(bev.i_kv, n), m_carry=_mask(bev.i_carry, n),
        m_latent=_mask(bev.i_latent, n),
        pair_a=i(bev.scan_pairs[:, 0]), pair_b=i(bev.scan_pairs[:, 1]),
        node_valid=np.ones(n, bool), n_valid=np.asarray(n, np.int64),
    )


@_trace.traced("accel.build_static_spec")
def build_static_spec(bev, *, pad_nodes: Optional[int] = None
                      ) -> StaticSpec:
    """Pure-host construction of the trace-shaping spec (no jax needed).

    This is the static-analysis hook: ``repro.analysis.recompile_lint``
    builds specs for a whole (arch, platform, objective) example grid —
    in the no-jax CI lane too — and flags any field whose value varies
    across the grid, i.e. data that should have been a ``DeviceArrays``
    leaf. ``lower_program`` routes through here so the linted spec and
    the spec that actually keys the XLA executable cache can never drift.
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
    )


@_trace.traced("accel.problem_fingerprint")
def problem_fingerprint(problem) -> str:
    """Canonical content hash of a Problem's lowered program (no jax).

    Routes through ``build_static_spec`` — the same keying path that
    shapes the XLA executable cache and that ``recompile_lint`` audits —
    and then hashes the host record ``lower_program`` ships to the
    device: the per-node workload quantities, kind masks, scan pairs,
    platform scalars, fold-realisability cube/lut, plus the Eq. 5
    objective flag and Eq. 4 amortisation factor. Two problems with
    equal fingerprints lower to bit-identical device programs (at any
    shared padding — padding is excluded on purpose: it is bit-neutral by
    the lowering contract, so it cannot change results), and therefore
    every deterministic engine returns identical designs, objectives and
    histories for them. This is the keying contract the service cache
    (``repro/service/cache.py``) and the ``optimise_portfolio``
    duplicate-coalescing fix rely on (docs/service.md documents it).

    Accepts a ``Problem`` (lowers via its cached ``batched()``) or a
    ``BatchedEvaluator`` directly. Pure host, jax-free.
    """
    bev = problem.batched() if hasattr(problem, "batched") else problem
    h = hashlib.sha256(b"repro.problem_fingerprint.v2")
    h.update(repr(dataclasses.astuple(build_static_spec(bev))).encode())
    for name, a in bev.arrays._asdict().items():
        if a is None:
            continue
        a = np.ascontiguousarray(a)
        h.update(f"|{name}:{a.dtype.str}:{a.shape}|".encode())
        h.update(a.tobytes())
    if bev.arrays.real_table is None:
        # menus too large for a dense cube (numpy-engine-only platforms):
        # the fold menu plus the platform name pins the candidate space —
        # a false MISS is possible across renamed-but-identical platforms,
        # a false HIT is not
        h.update(np.asarray(bev.platform.fold_values(), np.int64).tobytes())
        h.update(f"|platform:{bev.platform.name}|".encode())
    return h.hexdigest()


#: per-node fields padded with something other than 0 / False (the fold
#: divisors and counts of a neutral node are 1; scan group -1 is "none")
_PAD_FILL = {"batch": 1, "rows": 1, "cols": 1, "col_div": 1,
             "scan_group": -1, "val_lut": -1}


def _pad1(a: np.ndarray, n_pad: int, fill) -> np.ndarray:
    """Pad a per-node (or per-edge) 1-D array to ``n_pad`` with ``fill``."""
    if len(a) >= n_pad:
        return a
    out = np.full(n_pad, fill, a.dtype)
    out[:len(a)] = a
    return out


@_trace.traced("accel.lower_program")
def lower_program(bev, *, pad_nodes: Optional[int] = None,
                  pad_pairs: Optional[int] = None,
                  pad_vals: Optional[int] = None,
                  pad_lut: Optional[int] = None
                  ) -> Tuple[StaticSpec, DeviceArrays]:
    """The host record (``bev.arrays``) padded and moved onto the default
    jax device at its dtype (float32/int32, or float64/int64 under x64).

    ``pad_nodes``/``pad_pairs`` pad the node axis / scan-pair list so
    problems of different sizes can share one StaticSpec (fleet sweeps);
    padded columns are neutral and provably cannot change any result.
    The pair list is padded to at least one (0, 0) self-pair.
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

    host = bev.arrays
    if host.real_table is None:
        raise EngineUnavailable(
            f"platform {bev.platform.name!r} has "
            f"{len(bev.platform.fold_values())} fold values; the jax "
            f"engine needs a dense realisability table (<= "
            f"{MAX_TABLE_VALUES} values) or an AbstractPlatform product "
            f"rule. Use engine='numpy' for this platform.")
    static = build_static_spec(bev, pad_nodes=pad_nodes)
    np_ = static.n_nodes

    def pad_to(given, have: int, what: str) -> int:
        want = have if given is None else int(given)
        if want < have:
            raise ValueError(f"{what}={want} < {have}")
        return want

    nv = host.real_table.shape[0]
    pv = pad_to(pad_vals, nv, "pad_vals")
    table = np.zeros((pv, pv, pv), bool)
    table[:nv, :nv, :nv] = host.real_table
    n_pairs = len(host.pair_a)
    pp = pad_to(max(n_pairs, 1) if pad_pairs is None else pad_pairs,
                n_pairs, "pad_pairs")
    length = {"cut_allowed": max(np_ - 1, 0), "pair_a": pp, "pair_b": pp,
              "val_lut": pad_to(pad_lut, len(host.val_lut), "pad_lut")}

    out = {}
    for name, a in host._asdict().items():
        if name == "real_table":
            a = table
        elif a.ndim == 1:
            a = _pad1(a, length.get(name, np_), _PAD_FILL.get(name, 0))
        dt = {"f": fdt, "i": idt}.get(a.dtype.kind)
        out[name] = jnp.asarray(a, dt)
    return static, DeviceArrays(**out)
