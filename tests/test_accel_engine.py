"""Accelerator-resident search engine (core/accel/).

Three-way engine agreement: the jitted jax array program must match the
numpy engine AND the scalar reference on objective, feasibility, partition
times and Eq. 6 residency across every example architecture, mode and
backend.

Precision contract (documented in core/accel/eval_jax.py): with jax's
default float32 device arrays the jax engine agrees with the float64
reference to ~1e-7 relative (we assert 1e-5 for headroom) and feasibility
is exact — the binding constraints are integer-exact (divisibility, mesh
realisability, matching) or sit far from their float thresholds on the
example spaces. With float64 (``jax.config.update("jax_enable_x64",
True)``, exercised here through the ``enable_x64`` context manager) the
agreement tightens to the numpy engine's own 1e-9 contract.
"""
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch, reduced
from repro.configs.base import ShapeSpec
from repro.core.accel import (
    ENGINES,
    EngineUnavailable,
    jax_available,
    resolve_engine,
)
from repro.core.backends import BACKENDS
from repro.core.graph_builder import build_hdgraph
from repro.core.objectives import Problem
from repro.core.optimizers import (
    brute_force,
    rule_based,
    simulated_annealing,
)
from repro.core.perfmodel import ModelOptions
from repro.core.platform import Platform

jax = pytest.importorskip("jax")

from repro.core.accel.eval_jax import JaxEvaluator  # noqa: E402

PLAT = Platform(name="t-4x4", mesh_axes=(("data", 4), ("model", 4)))
TRAIN = ShapeSpec("train_tiny", 256, 16, "train")
PREFILL = ShapeSpec("prefill_tiny", 256, 16, "prefill")
DECODE = ShapeSpec("decode_tiny", 256, 16, "decode")

#: float32-on-device agreement vs the float64 scalar reference
F32_RTOL = 1e-5

EXAMPLE_ARCHS = sorted(ARCHS)


def _problem(arch_name, shape, backend="spmd", objective="throughput",
             exec_model="streaming", **opts) -> Problem:
    arch = reduced(get_arch(arch_name))
    graph = build_hdgraph(arch, shape)
    return Problem(graph=graph, platform=PLAT, backend=BACKENDS[backend],
                   objective=objective, exec_model=exec_model,
                   opts=ModelOptions(**opts))


def _random_designs(prob: Problem, n: int, seed: int = 0):
    import random
    rng = random.Random(seed)
    v = prob.backend.initial(prob.graph)
    out = []
    for _ in range(n):
        v = prob.backend.random_move(rng, prob.graph, v, prob.platform)
        out.append(v)
    return out


def _assert_three_way(prob: Problem, designs, rtol=F32_RTOL, atol=1e-12):
    """jax == numpy == scalar on the full result surface."""
    bev = prob.batched()
    jev = JaxEvaluator.from_problem(prob)
    packed = bev.pack(designs)
    rn = bev.evaluate_batch(*packed)
    rj = jev.evaluate_batch(*packed)
    # jax vs numpy (whole batch at once)
    np.testing.assert_array_equal(rj.feasible, rn.feasible)
    np.testing.assert_allclose(rj.objective, rn.objective,
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(rj.part_times, rn.part_times,
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(rj.node_resident, rn.node_resident,
                               rtol=rtol)
    np.testing.assert_allclose(rj.node_collective, rn.node_collective,
                               rtol=rtol, atol=1e-6)
    # jax vs the scalar reference, design by design
    for r, v in enumerate(designs):
        ev = prob.evaluate(v)
        assert ev.feasible == bool(rj.feasible[r])
        assert ev.objective == pytest.approx(rj.objective[r], rel=rtol)
        np.testing.assert_allclose(
            ev.partition_times,
            rj.part_times[r][:int(rj.nparts[r])], rtol=rtol, atol=atol)
        np.testing.assert_allclose(
            [e.hbm_resident for e in ev.node_evals],
            rj.node_resident[r], rtol=rtol)


@pytest.mark.parametrize("arch_name", EXAMPLE_ARCHS)
def test_jax_matches_numpy_and_scalar_all_example_archs(arch_name):
    prob = _problem(arch_name, TRAIN, backend="spmd")
    _assert_three_way(prob, _random_designs(prob, 25, seed=1))


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("shape", [TRAIN, PREFILL, DECODE],
                         ids=lambda s: s.mode)
def test_jax_matches_all_modes_and_backends(backend, shape):
    prob = _problem("tinyllama-1.1b", shape, backend=backend)
    _assert_three_way(prob, _random_designs(prob, 20, seed=2))


@pytest.mark.slow
def test_jax_matches_objectives_exec_models_and_options():
    for objective in ("latency", "throughput"):
        for exec_model in ("streaming", "spmd"):
            prob = _problem("tinyllama-1.1b", TRAIN, objective=objective,
                            exec_model=exec_model)
            _assert_three_way(prob, _random_designs(prob, 20, seed=3))
    prob = _problem("tinyllama-1.1b", TRAIN, zero1=True,
                    grad_compression=0.25, overlap_collectives=0.5,
                    seq_parallel_stash=True)
    _assert_three_way(prob, _random_designs(prob, 20, seed=4))


def test_jax_float64_matches_at_1e9():
    """x64 on-device arrays recover the numpy engine's 1e-9 contract."""
    with jax.enable_x64(True):
        prob = _problem("tinyllama-1.1b", TRAIN)
        designs = _random_designs(prob, 20, seed=5)
        jev = JaxEvaluator.from_problem(prob)
        assert str(jev.arrays.flops.dtype) == "float64"
        _assert_three_way(prob, designs, rtol=1e-9, atol=1e-15)


# ----------------------------------------------------------------------
# on-device search loops
# ----------------------------------------------------------------------

def test_brute_force_jax_equals_numpy_engine():
    """Identical enumeration: same optimum design, same point count, same
    improvement history (indices exact; objectives at f32 rounding)."""
    for backend in ("simple", "megatron"):
        for include_cuts in (False, True):
            a = brute_force(_problem("tinyllama-1.1b", TRAIN,
                                     backend=backend),
                            include_cuts=include_cuts, engine="numpy",
                            batch_size=256)
            b = brute_force(_problem("tinyllama-1.1b", TRAIN,
                                     backend=backend),
                            include_cuts=include_cuts, engine="jax",
                            batch_size=256)
            assert a.points == b.points
            assert a.variables == b.variables
            assert [i for i, _ in a.history] == [i for i, _ in b.history]
            for (_, oa), (_, ob) in zip(a.history, b.history):
                assert oa == pytest.approx(ob, rel=F32_RTOL)
            # the returned evaluation re-derives through the scalar
            # reference, so the engines' reported optima are bit-identical
            assert a.evaluation.objective == b.evaluation.objective


def test_brute_force_jax_respects_max_points():
    res = brute_force(_problem("tinyllama-1.1b", TRAIN), max_points=100,
                      engine="jax", batch_size=64)
    assert res.points == 100


def test_device_sa_deterministic_and_feasible():
    """Fixed seed => identical design and history; incumbents are feasible
    under the scalar reference; different seeds explore differently."""
    kw = dict(max_iters=300, chains=4, engine="jax")
    r1 = simulated_annealing(_problem("tinyllama-1.1b", TRAIN), seed=7, **kw)
    r2 = simulated_annealing(_problem("tinyllama-1.1b", TRAIN), seed=7, **kw)
    r3 = simulated_annealing(_problem("tinyllama-1.1b", TRAIN), seed=8, **kw)
    assert r1.variables == r2.variables
    assert r1.history == r2.history
    assert r1.evaluation.feasible and r3.evaluation.feasible
    assert r1.points >= 300


def test_device_sa_per_chain_incumbents():
    from repro.core.optimizers.common import repair
    from repro.core.accel.search_loops import DeviceSA
    import jax.numpy as jnp

    prob = _problem("tinyllama-1.1b", TRAIN)
    sa = DeviceSA(prob)
    v0 = repair(prob, prob.backend.initial(prob.graph))
    ev0 = prob.evaluate(v0)
    state = sa.init_state(v0, ev0, chains=3, seed=11)
    temps = jnp.asarray([1000.0, 1600.0, 2560.0])
    state, temps, _ = sa.run(state, temps,
                             scale=max(abs(ev0.objective), 1e-12) / 1000.0,
                             cooling=0.98, k_min=1.0, n_sweeps=150)
    incumbents = sa.best_variables(state)
    assert len(incumbents) == 3
    for v, obj, feas in incumbents:
        ev = prob.evaluate(v)            # device state round-trips exactly
        assert ev.feasible == feas
        if feas:
            assert ev.objective == pytest.approx(obj, rel=F32_RTOL)
            assert ev.objective <= ev0.objective + 1e-12


# ----------------------------------------------------------------------
# padded lowering (the fleet bucketing contract)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_padded_lowering_bitwise_identical(backend):
    """Padding the node axis must be bitwise neutral — the property the
    fleet engine relies on to stack differently-sized graphs."""
    prob = _problem("tinyllama-1.1b", TRAIN, backend=backend)
    designs = _random_designs(prob, 25, seed=9)
    bev = prob.batched()
    packed = bev.pack(designs)
    r0 = JaxEvaluator(bev).evaluate_batch(*packed)
    rp = JaxEvaluator(bev, pad_nodes=bev.n_nodes + 5).evaluate_batch(*packed)
    np.testing.assert_array_equal(r0.objective, rp.objective)
    np.testing.assert_array_equal(r0.feasible, rp.feasible)
    np.testing.assert_array_equal(r0.part_times, rp.part_times)
    np.testing.assert_array_equal(r0.node_resident, rp.node_resident)
    np.testing.assert_array_equal(r0.node_collective, rp.node_collective)


def test_padded_lowering_stages_the_last_real_node():
    """The last partition stages its last REAL node's featuremap (Eq. 7
    boundary bytes), not a padded column's. Here the head is a partition
    of its own whose staged featuremap, in and out, just exceeds the
    platform's HBM bandwidth over its (compute-bound) time: padded and
    unpadded lowerings must both find the design infeasible, as the
    scalar reference does."""
    from repro.core.hdgraph import HDGraph, Node, Variables
    common = dict(rows=64, cols=64, batch=8, fm_width=64,
                  weight_bytes=8192.0, act_bytes=1024.0)
    graph = HDGraph([Node("l0.ffn", "ffn", 0, flops=1e13, **common),
                     Node("head", "head", 1, flops=1e12, **common)],
                    "two", "t", "prefill")
    plat = Platform(name="t-4x4-slow-hbm", hbm_bw=1e6,
                    mesh_axes=(("data", 4), ("model", 4)))
    prob = Problem(graph=graph, platform=plat, backend=BACKENDS["spmd"],
                   objective="throughput", exec_model="streaming",
                   opts=ModelOptions())
    v = Variables((0,), (1, 1), (1, 1), (1, 1))
    assert any("bandwidth" in x for x in prob.check(v).violations)
    bev = prob.batched()
    packed = bev.pack([v])
    for pad in (None, 5):
        res = JaxEvaluator(bev, pad_nodes=pad).evaluate_batch(*packed)
        assert not res.feasible[0], pad


# ----------------------------------------------------------------------
# fleet sweeps (core/accel/fleet.py): vmapped multi-problem search
# ----------------------------------------------------------------------

def _assert_bf_identical(names, shape=TRAIN, backend="spmd", **kw):
    from repro.core.accel.fleet import fleet_brute_force

    loop = [brute_force(_problem(n, shape, backend=backend),
                        engine="jax", **kw) for n in names]
    fleet = fleet_brute_force([_problem(n, shape, backend=backend)
                               for n in names], **kw)
    for n, a, b in zip(names, loop, fleet):
        assert a.points == b.points, n
        assert a.variables == b.variables, n
        assert a.history == b.history, n
        # both re-derive the evaluation through the float64 scalar
        # reference, so the reported optima are bit-identical
        assert a.evaluation.objective == b.evaluation.objective, n


def test_fleet_brute_force_identical_to_loop():
    """Mixed-size portfolio in one bucket: per-problem optimum, point
    count and improvement history identical to the per-problem engine."""
    _assert_bf_identical(EXAMPLE_ARCHS[:3], include_cuts=True,
                         max_points=2000, batch_size=256)


@pytest.mark.slow
def test_fleet_brute_force_all_example_archs():
    """Acceptance: optimise_portfolio over ALL example archs returns
    per-problem optima identical to per-problem jax loops."""
    _assert_bf_identical(EXAMPLE_ARCHS, include_cuts=True,
                         max_points=1500, batch_size=256)


@pytest.mark.parametrize("backend", ["spmd", "megatron"])
def test_fleet_annealing_identical_to_loop(backend):
    """Vmapped device SA consumes the identical random stream as the
    per-problem sweep (chain-shaped draws only), so fleet trajectories are
    bit-identical — including on strict-KV backends where the on-device
    repair path is active."""
    from repro.core.accel.fleet import fleet_annealing

    names = EXAMPLE_ARCHS[:3]
    kw = dict(seed=11, max_iters=150, chains=3)
    loop = [simulated_annealing(_problem(n, TRAIN, backend=backend),
                                engine="jax", **kw) for n in names]
    fleet = fleet_annealing([_problem(n, TRAIN, backend=backend)
                             for n in names], **kw)
    for n, a, b in zip(names, loop, fleet):
        assert a.variables == b.variables, n
        assert a.history == b.history, n
        assert a.evaluation.objective == b.evaluation.objective, n


def test_optimise_portfolio_matches_loop_plans():
    from repro.core.pipeline import optimise_mapping, optimise_portfolio

    archs = [reduced(get_arch(n)) for n in EXAMPLE_ARCHS[:3]]
    kw = dict(optimiser="brute_force", max_points=1000, batch_size=256)
    plans = optimise_portfolio(archs, TRAIN, PLAT, **kw)
    loops = [optimise_mapping(a, TRAIN, PLAT, engine="jax", **kw)
             for a in archs]
    for pl, lp in zip(plans, loops):
        assert pl.objective_value == lp.objective_value
        assert pl.latency == lp.latency
        assert pl.throughput == lp.throughput
        assert [p.node_indices for p in pl.partitions] \
            == [p.node_indices for p in lp.partitions]


# ----------------------------------------------------------------------
# device rule-based (Algorithm 2): the greedy descent as one jitted loop
# ----------------------------------------------------------------------

def _assert_rb_identical(a, b, label=""):
    """Scalar-reference identity on the full result surface: same merge
    sequence (the history indices record every accepted merge), same
    probe count, same final design, same objective (both re-derived
    through the float64 scalar reference — bit-identical)."""
    assert a.points == b.points, label
    assert a.variables == b.variables, label
    assert a.history == b.history, label
    assert a.evaluation.objective == b.evaluation.objective, label


def test_rule_based_jax_equals_scalar_reference():
    """The device descent chooses the bit-identical move sequence to the
    scalar reference: final design, probe count, merge history and
    objective all match, across backends and objectives."""
    for backend in sorted(BACKENDS):
        for objective in ("latency", "throughput"):
            a = rule_based(_problem("tinyllama-1.1b", TRAIN, backend=backend,
                                    objective=objective), engine="scalar")
            b = rule_based(_problem("tinyllama-1.1b", TRAIN, backend=backend,
                                    objective=objective), engine="jax")
            _assert_rb_identical(a, b, (backend, objective))


@pytest.mark.slow
@pytest.mark.parametrize("arch_name", EXAMPLE_ARCHS)
def test_rule_based_jax_equals_scalar_all_example_archs(arch_name):
    """Acceptance: bit-identical merge sequence, final design and
    objective vs the scalar reference on EVERY example arch."""
    a = rule_based(_problem(arch_name, TRAIN), engine="scalar")
    b = rule_based(_problem(arch_name, TRAIN), engine="jax")
    _assert_rb_identical(a, b, arch_name)


def test_rule_based_descend_single_trace(assert_max_traces):
    """One greedy descent = ONE jitted lax.while_loop program (probe
    construction, evaluation, argmax selection and the step loop), traced
    once per problem family and reused across descents, partitions and
    problems — zero host evaluations while it runs."""
    from repro.core.accel.search_loops import DeviceRuleBased
    from repro.core.hdgraph import partitions_from_cuts
    from repro.core.optimizers.common import repair

    prob = _problem("tinyllama-1.1b", TRAIN)
    rb = DeviceRuleBased(prob)
    v0 = repair(prob, prob.backend.initial(prob.graph))
    part = partitions_from_cuts(prob.graph, v0.cuts)[0]
    with assert_max_traces(1, keys=("rb_descend",)):
        v1, pts1 = rb.descend(v0, part)
        evals_before = prob.evals_done
        v2, pts2 = rb.descend(v0, part)      # same request: no retrace
        assert prob.evals_done == evals_before + pts2  # only the batch note
    assert v1 == v2 and pts1 == pts2
    assert pts1 > 0


@pytest.mark.parametrize("arch_name", ["tinyllama-1.1b", "stablelm-3b"])
def test_rule_based_descend_packed_one_copy_each_way(arch_name):
    """``descend`` sends one packed request and reads back one packed
    answer, and answers exactly what the unpacked descent core does
    (fed the ``pack_request`` arguments one array each) and what the
    numpy engine's ``optimise_partition`` does, on the first, a middle
    and the last partition of a repaired initial design."""
    import jax.numpy as jnp

    from repro.core.accel.search_loops import (
        DeviceRuleBased,
        _rb_descend_core,
    )
    from repro.core.hdgraph import partitions_from_cuts
    from repro.core.optimizers.common import repair
    from repro.core.optimizers.rule_based import optimise_partition
    from repro.obs import metrics

    prob = _problem(arch_name, TRAIN)
    rb = DeviceRuleBased(prob)
    idt, fdt = rb.A.batch.dtype, rb.A.flops.dtype
    core = jax.jit(_rb_descend_core, static_argnums=(0, 1))
    v0 = repair(prob, prob.backend.initial(prob.graph))
    parts = partitions_from_cuts(prob.graph, v0.cuts)
    picks = sorted({0, len(parts) // 2, len(parts) - 1})
    assert len(parts) >= 2 and len(picks) >= 2
    counters = metrics.snapshot()["counters"]
    transfers0 = counters.get("accel.transfers.rb_descend", 0)
    dispatches0 = counters.get("accel.dispatches.rb_descend", 0)
    for i in picks:
        part = parts[i]
        v1, pts1 = rb.descend(v0, part)
        si, so, kk, cb_row, part_mask, pidx, cap = rb.pack_request(v0, part)
        out = core(rb.static, rb.gran, rb.A, rb.menus, rb.menu_sizes,
                   rb.clamp, jnp.asarray(si, idt), jnp.asarray(so, idt),
                   jnp.asarray(kk, idt), jnp.asarray(cb_row),
                   jnp.asarray(part_mask), jnp.asarray(pidx, idt),
                   jnp.asarray(rb.amort, fdt), jnp.asarray(cap, idt))
        v2, pts2 = rb.unpack(v0, *(np.asarray(x) for x in out))
        assert (v1, pts1) == (v2, pts2), (arch_name, i)
        v3, _ = optimise_partition(prob, v0, part)
        assert v1 == v3, (arch_name, i)
    counters = metrics.snapshot()["counters"]
    dispatches = counters["accel.dispatches.rb_descend"] - dispatches0
    assert dispatches == len(picks)
    assert counters["accel.transfers.rb_descend"] - transfers0 \
        == 2 * dispatches


def test_fleet_rule_based_identical_to_loop(assert_max_traces):
    """A mixed-size portfolio advances its greedy descents in lockstep as
    ONE vmapped executable, with per-problem merge sequences, designs,
    histories and objectives identical to per-problem engine="jax" loops
    (hence to the scalar reference) — executable count < problem count."""
    from repro.core.accel.fleet import fleet_rule_based

    names = EXAMPLE_ARCHS[:3]
    probs = [_problem(n, TRAIN) for n in names]
    with assert_max_traces(1, keys=("fleet_rb_descend",)):
        fleet = fleet_rule_based(probs)
    loop = [rule_based(_problem(n, TRAIN), engine="jax") for n in names]
    scalar = [rule_based(_problem(n, TRAIN), engine="scalar")
              for n in names]
    for n, a, b, c in zip(names, loop, fleet, scalar):
        _assert_rb_identical(a, b, n)
        _assert_rb_identical(c, b, n)


def _rb_mixed_grid(names, plats, objectives):
    def probs():
        out = []
        for name, plat, obj in zip(names, plats, objectives):
            arch = reduced(get_arch(name))
            graph = build_hdgraph(arch, TRAIN)
            out.append(Problem(graph=graph, platform=plat,
                               backend=BACKENDS["spmd"], objective=obj,
                               exec_model="streaming", opts=ModelOptions()))
        return out
    return probs


def _assert_rb_fleet_matches_scalar(probs, assert_max_traces, n_probs):
    from repro.core.accel.fleet import bucket_indices, fleet_rule_based

    assert bucket_indices(probs(), tiered=False) == [list(range(n_probs))]
    # ONE executable for the whole mixed grid — fewer than problems
    with assert_max_traces(1, keys=("fleet_rb_descend",)):
        fleet = fleet_rule_based(probs())
    scalar = [rule_based(p, engine="scalar") for p in probs()]
    for i, (a, b) in enumerate(zip(scalar, fleet)):
        _assert_rb_identical(a, b, i)


def test_fleet_rule_based_mixed_platforms_and_objectives(assert_max_traces):
    """Acceptance: rule_based via the fleet over mixed platforms AND mixed
    objectives — one bucket, ONE executable (platform scalars, fold cubes,
    the Eq. 5 objective selector and Eq. 4 amortisation are all device
    data), per-problem results identical to the scalar reference."""
    names = [EXAMPLE_ARCHS[0], EXAMPLE_ARCHS[0], EXAMPLE_ARCHS[1],
             EXAMPLE_ARCHS[1]]
    plats = [PLAT, PLAT_2x8, PLAT_2x8, PLAT]
    objectives = ["throughput", "latency", "latency", "throughput"]
    _assert_rb_fleet_matches_scalar(
        _rb_mixed_grid(names, plats, objectives), assert_max_traces, 4)


@pytest.mark.slow
def test_fleet_rule_based_mixed_with_abstract_platform(assert_max_traces):
    """The mixed grid including an AbstractPlatform member (16-value fold
    menus — the largest probe batches, padded against mesh members)."""
    names = [EXAMPLE_ARCHS[0], EXAMPLE_ARCHS[0], EXAMPLE_ARCHS[1]]
    plats = [PLAT, PLAT_ABS, PLAT_2x8]
    objectives = ["throughput", "latency", "throughput"]
    _assert_rb_fleet_matches_scalar(
        _rb_mixed_grid(names, plats, objectives), assert_max_traces, 3)


# ----------------------------------------------------------------------
# objective/batch_amortisation as device data (the last bucket splitters)
# ----------------------------------------------------------------------

def test_optimise_portfolio_rule_based_mixed_objectives():
    """Acceptance: optimise_portfolio(optimiser="rule_based") over mixed
    platforms and mixed objectives matches per-problem
    optimise_mapping(engine="jax") — and hence the scalar reference —
    exactly."""
    from repro.core.pipeline import optimise_mapping, optimise_portfolio

    archs = [reduced(get_arch(n)) for n in EXAMPLE_ARCHS[:2]]
    plats = [PLAT, PLAT_2x8]
    objs = ["throughput", "latency"]
    plans = optimise_portfolio(archs, TRAIN, plats, optimiser="rule_based",
                               objective=objs, engine="jax")
    loops = [optimise_mapping(a, TRAIN, p, optimiser="rule_based",
                              objective=o, engine="jax")
             for a, p, o in zip(archs, plats, objs)]
    for pl, lp in zip(plans, loops):
        assert pl.objective_value == lp.objective_value
        assert pl.latency == lp.latency
        assert [pt.node_indices for pt in pl.partitions] \
            == [pt.node_indices for pt in lp.partitions]


def test_mixed_objectives_share_one_bucket_and_executable(
        assert_max_traces):
    """Problems differing ONLY in objective share a StaticSpec, a fleet
    bucket and a cached executable: the objective is selected by a traced
    where over device data, not baked into the trace."""
    from repro.core.accel.fleet import bucket_indices, fleet_brute_force

    def probs():
        return [_problem("tinyllama-1.1b", TRAIN, objective=o)
                for o in ("throughput", "latency", "throughput")]

    lat = JaxEvaluator.from_problem(_problem("tinyllama-1.1b", TRAIN,
                                             objective="latency"))
    thr = JaxEvaluator.from_problem(_problem("tinyllama-1.1b", TRAIN,
                                             objective="throughput"))
    assert lat.static == thr.static
    assert bool(lat.arrays.obj_latency) and not bool(thr.arrays.obj_latency)
    assert bucket_indices(probs()) == [[0, 1, 2]]

    # one fleet executable for the objective mix (batch sizes unique in
    # the suite so a previously cached executable cannot satisfy this)
    with assert_max_traces(1, keys=("fleet_bf_chunk",), exact=True):
        fleet = fleet_brute_force(probs(), include_cuts=False,
                                  max_points=500, batch_size=125)
    loop = [brute_force(p, engine="jax", include_cuts=False,
                        max_points=500, batch_size=125) for p in probs()]
    for a, b in zip(loop, fleet):
        assert a.variables == b.variables
        assert a.history == b.history


def test_mixed_batch_amortisation_shares_executable():
    """batch_amortisation no longer splits StaticSpecs either."""
    p1 = _problem("tinyllama-1.1b", TRAIN)
    p2 = _problem("tinyllama-1.1b", TRAIN)
    p2.batch_amortisation = 64
    j1, j2 = JaxEvaluator.from_problem(p1), JaxEvaluator.from_problem(p2)
    assert j1.static == j2.static
    assert float(j1.arrays.batch_amortisation) == 256.0
    assert float(j2.arrays.batch_amortisation) == 64.0
    # and the numbers still match the scalar reference per problem
    for p, j in ((p1, j1), (p2, j2)):
        designs = _random_designs(p, 8, seed=21)
        packed = p.batched().pack(designs)
        rj = j.evaluate_batch(*packed)
        for r, v in enumerate(designs):
            assert p.evaluate(v).objective == pytest.approx(
                rj.objective[r], rel=F32_RTOL)


# ----------------------------------------------------------------------
# heterogeneous-platform fleets: platform scalars as device data
# ----------------------------------------------------------------------

from repro.core.platform import AbstractPlatform  # noqa: E402

#: three platforms with different resource limits, bandwidth scalars AND
#: fold-menu sizes (mesh-4x4: 3 values; mesh-2x8: 4; abstract-16: 16) —
#: the mixed-fold-cube stacking case
PLAT_2x8 = Platform(name="t-2x8", mesh_axes=(("data", 2), ("model", 8)),
                    hbm_bytes=8 * 2**30, hbm_bw=400e9)
PLAT_ABS = AbstractPlatform(name="t-abs16",
                            mesh_axes=(("data", 4), ("model", 4)))
HETERO_PLATS = (PLAT, PLAT_2x8, PLAT_ABS)


def _hetero_problems(names, plats, shape=TRAIN, backend="spmd"):
    probs, pairs = [], []
    for name, plat in zip(names, plats):
        arch = reduced(get_arch(name))
        graph = build_hdgraph(arch, shape)
        probs.append(Problem(graph=graph, platform=plat,
                             backend=BACKENDS[backend],
                             objective="throughput",
                             exec_model="streaming", opts=ModelOptions()))
        pairs.append((name, plat.name))
    return probs, pairs


def test_mixed_platforms_bucket_together():
    """Bucketing keys on trace shape only: one bucket for one graph family
    across platforms with different scalars and fold-cube sizes."""
    from repro.core.accel.fleet import bucket_indices

    probs, _ = _hetero_problems(["tinyllama-1.1b"] * 3, HETERO_PLATS)
    assert bucket_indices(probs) == [[0, 1, 2]]
    assert bucket_indices(probs, tiered=False) == [[0, 1, 2]]
    # fold menus really do differ in size — the stacking pads them
    sizes = {len(p.platform.fold_values()) for p in probs}
    assert len(sizes) == 3


def test_padded_value_tables_bitwise_identical():
    """pad_vals / pad_lut (the mixed-fold-cube stacking contract) are
    bitwise neutral, like node padding."""
    prob = _problem("tinyllama-1.1b", TRAIN)
    designs = _random_designs(prob, 25, seed=13)
    bev = prob.batched()
    packed = bev.pack(designs)
    nv = len(prob.platform.fold_values())
    r0 = JaxEvaluator(bev).evaluate_batch(*packed)
    rp = JaxEvaluator(bev, pad_vals=nv + 13,
                      pad_lut=max(prob.platform.fold_values()) + 9
                      ).evaluate_batch(*packed)
    np.testing.assert_array_equal(r0.objective, rp.objective)
    np.testing.assert_array_equal(r0.feasible, rp.feasible)
    np.testing.assert_array_equal(r0.part_times, rp.part_times)
    np.testing.assert_array_equal(r0.node_resident, rp.node_resident)


@pytest.mark.parametrize("optimiser", ["brute_force", "annealing"])
def test_fleet_hetero_identical_to_loop(optimiser):
    """Acceptance: a mixed-platform portfolio (different limits, bandwidth
    scalars and fold-cube sizes) returns per-problem optima, objectives
    and histories bit-identical to per-problem engine="jax" loops, for
    both optimisers."""
    from repro.core.accel.fleet import fleet_annealing, fleet_brute_force

    names = [EXAMPLE_ARCHS[0], EXAMPLE_ARCHS[0], EXAMPLE_ARCHS[1],
             EXAMPLE_ARCHS[1]]
    plats = [PLAT, PLAT_ABS, PLAT_2x8, PLAT_ABS]
    probs, pairs = _hetero_problems(names, plats)
    if optimiser == "brute_force":
        kw = dict(include_cuts=True, max_points=2000, batch_size=256)
        loop = [brute_force(p, engine="jax", **kw)
                for p in _hetero_problems(names, plats)[0]]
        fleet = fleet_brute_force(probs, **kw)
        for pair, a, b in zip(pairs, loop, fleet):
            assert a.points == b.points, pair
    else:
        kw = dict(seed=17, max_iters=120, chains=3)
        loop = [simulated_annealing(p, engine="jax", **kw)
                for p in _hetero_problems(names, plats)[0]]
        fleet = fleet_annealing(probs, **kw)
    for pair, a, b in zip(pairs, loop, fleet):
        assert a.variables == b.variables, pair
        assert a.history == b.history, pair
        # both re-derive through the float64 scalar reference
        assert a.evaluation.objective == b.evaluation.objective, pair


def test_fleet_hetero_single_executable(assert_max_traces):
    """Trace-count acceptance: a portfolio spanning three platforms
    compiles FEWER executables than platforms — the platform axis is
    data, so the whole mixed grid is one traced program per bucket."""
    from repro.core.accel.fleet import fleet_annealing, fleet_brute_force

    probs, _ = _hetero_problems(["tinyllama-1.1b"] * 3, HETERO_PLATS)
    # chains/sweeps/batch sizes unique in the suite so a previously cached
    # executable cannot satisfy these calls
    with assert_max_traces(1, keys=("fleet_bf_chunk",), exact=True):
        fleet_brute_force(probs, include_cuts=False, max_points=600,
                          batch_size=128)

    probs, _ = _hetero_problems(["tinyllama-1.1b"] * 3, HETERO_PLATS)
    with assert_max_traces(1, keys=("fleet_sa_sweeps",), exact=True):
        fleet_annealing(probs, seed=3, max_iters=76, chains=2)


def test_optimise_portfolio_heterogeneous_platforms():
    """optimise_portfolio accepts per-problem platforms and matches the
    per-problem optimise_mapping(engine="jax") plans exactly."""
    from repro.core.pipeline import optimise_mapping, optimise_portfolio

    archs = [reduced(get_arch(n)) for n in EXAMPLE_ARCHS[:3]]
    plats = [PLAT, PLAT_2x8, PLAT_ABS]
    kw = dict(optimiser="brute_force", max_points=1000, batch_size=256)
    plans = optimise_portfolio(archs, TRAIN, plats, **kw)
    loops = [optimise_mapping(a, TRAIN, p, engine="jax", **kw)
             for a, p in zip(archs, plats)]
    for pl, lp in zip(plans, loops):
        assert pl.objective_value == lp.objective_value
        assert pl.latency == lp.latency
        assert pl.throughput == lp.throughput
        assert [pt.node_indices for pt in pl.partitions] \
            == [pt.node_indices for pt in lp.partitions]


# ----------------------------------------------------------------------
# on-device SA repair: zero host round-trips mid-sweep
# ----------------------------------------------------------------------

def test_device_sa_zero_host_roundtrips(assert_max_traces):
    """The whole sweep — proposal, repair, evaluate, accept — is ONE
    jitted lax.scan program: exactly one trace for a multi-sweep run, no
    retrace on resume, and zero host evaluations while it runs."""
    import jax.numpy as jnp
    from repro.core.accel.search_loops import DeviceSA
    from repro.core.optimizers.common import repair

    prob = _problem("tinyllama-1.1b", TRAIN, backend="megatron")
    sa = DeviceSA(prob)
    v0 = repair(prob, prob.backend.initial(prob.graph))
    ev0 = prob.evaluate(v0)
    # chains=5 / n_sweeps=41 are unique in the suite, so the executable
    # cannot have been compiled by an earlier test
    state = sa.init_state(v0, ev0, chains=5, seed=0)
    temps = jnp.asarray([1000.0 * (1.6 ** c) for c in range(5)])
    scale = max(abs(ev0.objective), 1e-12) / 1000.0

    evals_before = prob.evals_done
    with assert_max_traces(1, keys=("sa_sweeps",), exact=True):
        state, temps, _ = sa.run(state, temps, scale, 0.98, 1.0, n_sweeps=41)
        jax.block_until_ready(state["obj"])
        # resuming with the same shapes reuses the executable: no retrace,
        # still no host round-trips
        for _ in range(2):
            state, temps, _ = sa.run(state, temps, scale, 0.98, 1.0,
                                     n_sweeps=41)
            jax.block_until_ready(state["obj"])
    assert prob.evals_done == evals_before     # repair never left the device


def test_repair_jax_clamps_strict_kv():
    """The masked clamp-and-propagate step removes strict-KV violations on
    device and returns a design consistent under the backend's matching
    and tying rules."""
    import jax.numpy as jnp
    from repro.core.accel.search_loops import DeviceSA, propagate_jax, \
        repair_jax
    from repro.core.optimizers.common import repair

    prob = _problem("tinyllama-1.1b", TRAIN, backend="megatron")
    sa = DeviceSA(prob)
    kvl = np.asarray(sa.A.kv_limit)
    assert (kvl > 0).any(), "arch must have KV-limited nodes"
    v0 = repair(prob, prob.backend.initial(prob.graph))
    n = sa.static.n_nodes
    si = jnp.asarray(np.array(v0.s_in, np.int64)[None, :])
    kk = jnp.asarray(np.array(v0.kern, np.int64)[None, :])
    so = jnp.asarray(np.where(kvl > 0, 2 * kvl,
                              np.array(v0.s_out, np.int64))[None, :])
    cb = jnp.zeros((1, max(n - 1, 0)), bool)
    assert bool(((np.asarray(so) > kvl) & (kvl > 0)).any())
    r_si, r_so, r_kk = repair_jax(sa.static, sa.A, sa.kv_fix, si, so, kk, cb)
    r_so_np = np.asarray(r_so)
    assert not ((kvl > 0) & (r_so_np > kvl)).any()
    # repaired design is a fixed point of propagation (tying consistent)
    p_si, p_so, p_kk = propagate_jax(sa.static, sa.A, r_si, r_so, r_kk, cb)
    np.testing.assert_array_equal(np.asarray(p_si), np.asarray(r_si))
    np.testing.assert_array_equal(np.asarray(p_so), r_so_np)
    np.testing.assert_array_equal(np.asarray(p_kk), np.asarray(r_kk))


# ----------------------------------------------------------------------
# engine registry
# ----------------------------------------------------------------------

def test_registry_resolution():
    assert set(ENGINES) == {"scalar", "numpy", "jax"}
    assert resolve_engine("batched") == "numpy"     # legacy alias
    assert resolve_engine("scalar") == "scalar"
    assert resolve_engine("auto") in ("jax", "numpy")
    if jax_available():
        assert resolve_engine("auto") == "jax"
    with pytest.raises(ValueError, match="unknown engine"):
        resolve_engine("cuda")


def test_registry_names_missing_extra(monkeypatch):
    """Without jax the registry raises a clear EngineUnavailable naming the
    missing extra, instead of an ImportError mid-search."""
    import repro.core.accel as accel
    monkeypatch.setattr(accel, "jax_available", lambda: False)
    with pytest.raises(EngineUnavailable, match="jax"):
        accel.resolve_engine("jax")
    assert accel.resolve_engine("auto") == "numpy"
    with pytest.raises(EngineUnavailable, match="pip install jax"):
        accel.require_jax()


def test_optimisers_validate_engine_names(monkeypatch):
    """All three optimiser entry points reject unknown engines, and an
    explicit engine="jax" without jax raises EngineUnavailable rather than
    silently degrading."""
    from repro.core.optimizers import rule_based

    prob = _problem("tinyllama-1.1b", TRAIN, backend="simple")
    with pytest.raises(ValueError, match="unknown engine"):
        brute_force(prob, engine="nupmy")
    with pytest.raises(ValueError, match="unknown engine"):
        simulated_annealing(prob, engine="cuda", max_iters=1)
    with pytest.raises(ValueError, match="unknown engine"):
        rule_based(prob, engine="cuda")
    import repro.core.accel as accel
    monkeypatch.setattr(accel, "jax_available", lambda: False)
    for call in (lambda: brute_force(prob, engine="jax", max_points=1),
                 lambda: rule_based(prob, engine="jax")):
        with pytest.raises(EngineUnavailable):
            call()


def test_exporter_lazy_pspec_cached():
    from repro.core.exporter import _pspec
    from jax.sharding import PartitionSpec
    assert _pspec() is PartitionSpec
    assert _pspec() is _pspec()
