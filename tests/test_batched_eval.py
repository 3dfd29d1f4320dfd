"""Batched design-space evaluation engine (core/batched_eval.py).

The scalar perfmodel/objectives path is the reference implementation; the
batched array program must agree with it within 1e-9 on objective,
feasibility, partition times and Eq. 6 residency — across every example
architecture, mode, backend and objective, over randomly sampled fold/cut
designs. Also covers batched brute-force == scalar brute-force and
multi-chain annealing determinism.
"""
import itertools
import random

import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES_BY_NAME, get_arch, reduced
from repro.configs.base import ShapeSpec
from repro.core.array_program import _realizable, eval_core
from repro.core.backends import BACKENDS
from repro.core.batched_eval import NumpySegments
from repro.core.graph_builder import build_hdgraph
from repro.core.hdgraph import Variables
from repro.core.objectives import Problem
from repro.core.optimizers import brute_force, simulated_annealing
from repro.core.perfmodel import ModelOptions
from repro.core.platform import V5E_POD, Platform

PLAT = Platform(name="t-4x4", mesh_axes=(("data", 4), ("model", 4)))

TRAIN = ShapeSpec("train_tiny", 256, 16, "train")
PREFILL = ShapeSpec("prefill_tiny", 256, 16, "prefill")
DECODE = ShapeSpec("decode_tiny", 256, 16, "decode")

# every example architecture family in the zoo, reduced to test size
EXAMPLE_ARCHS = sorted(ARCHS)


def _problem(arch_name, shape, backend="spmd", objective="latency",
             exec_model="streaming", platform=PLAT, **opts) -> Problem:
    arch = reduced(get_arch(arch_name))
    graph = build_hdgraph(arch, shape)
    return Problem(graph=graph, platform=platform,
                   backend=BACKENDS[backend], objective=objective,
                   exec_model=exec_model, opts=ModelOptions(**opts))


def _random_designs(prob: Problem, n: int, seed: int = 0):
    """Designs from the backend's own move kernel (exercises cuts + folds)."""
    rng = random.Random(seed)
    g, be, plat = prob.graph, prob.backend, prob.platform
    v = be.initial(g)
    out = []
    for _ in range(n):
        v = be.random_move(rng, g, v, plat)
        out.append(v)
    return out


def _assert_match(prob: Problem, designs):
    res = prob.evaluate_many(designs)
    for r, v in enumerate(designs):
        ev = prob.evaluate(v)
        assert ev.feasible == bool(res.feasible[r]), \
            f"feasibility mismatch at {r}: {ev.violations}"
        assert ev.objective == pytest.approx(res.objective[r],
                                             rel=1e-9, abs=1e-15)
        assert ev.latency == pytest.approx(res.latency[r],
                                           rel=1e-9, abs=1e-15)
        np.testing.assert_allclose(
            ev.partition_times,
            res.part_times[r][:int(res.nparts[r])], rtol=1e-9, atol=1e-15)
        np.testing.assert_allclose(
            [e.hbm_resident for e in ev.node_evals],
            res.node_resident[r], rtol=1e-9)


@pytest.mark.parametrize("arch_name", EXAMPLE_ARCHS)
def test_batched_matches_scalar_all_example_archs(arch_name):
    """Property: batched == scalar over random designs for every example
    config, in its most general setting (spmd backend, streaming)."""
    prob = _problem(arch_name, TRAIN, backend="spmd",
                    objective="throughput", exec_model="streaming")
    _assert_match(prob, _random_designs(prob, 40, seed=1))


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("shape", [TRAIN, PREFILL, DECODE],
                         ids=lambda s: s.mode)
def test_batched_matches_scalar_modes_and_backends(backend, shape):
    for objective in ("latency", "throughput"):
        for exec_model in ("streaming", "spmd"):
            prob = _problem("tinyllama-1.1b", shape, backend=backend,
                            objective=objective, exec_model=exec_model)
            _assert_match(prob, _random_designs(prob, 25, seed=2))


def test_batched_matches_scalar_model_options():
    """ZeRO-1, gradient compression, collective overlap and Megatron-SP
    stash all flow through the lowering."""
    prob = _problem("tinyllama-1.1b", TRAIN, backend="spmd",
                    zero1=True, grad_compression=0.25,
                    overlap_collectives=0.5, seq_parallel_stash=True)
    _assert_match(prob, _random_designs(prob, 25, seed=3))


def test_batched_matches_scalar_moe_and_rwkv():
    """MoE (ep_alltoall) and recurrent (carry_bytes) collectives."""
    for name in ("granite-moe-1b-a400m", "rwkv6-1.6b"):
        for shape in (TRAIN, DECODE):
            prob = _problem(name, shape, backend="spmd",
                            objective="throughput")
            _assert_match(prob, _random_designs(prob, 25, seed=4))


#: the benchmark's configurations at full depth, each at its cells' shapes
BENCHMARK_GRAPHS = [("stablelm-3b", "train_4k"), ("stablelm-3b", "prefill_32k"),
                    ("jamba-1.5-large-398b", "train_4k"),
                    ("jamba-1.5-large-398b", "prefill_32k"),
                    ("kimi-k2-1t-a32b", "prefill_32k"),
                    ("kimi-k2-1t-a32b", "decode_32k")]


@pytest.mark.parametrize("arch_name,shape_name", BENCHMARK_GRAPHS)
def test_batched_matches_scalar_benchmark_graphs(arch_name, shape_name):
    """The numpy engine at the benchmark's own sizes: the published
    widths and depths on the V5E pod, random designs with cuts."""
    graph = build_hdgraph(get_arch(arch_name), SHAPES_BY_NAME[shape_name])
    prob = Problem(graph=graph, platform=V5E_POD, backend=BACKENDS["spmd"],
                   objective="latency", exec_model="spmd")
    designs = _random_designs(prob, 30, seed=9)
    assert sum(bool(v.cuts) for v in designs) >= 5
    _assert_match(prob, designs)


def _program(bev, designs, single_partition, **kw):
    si, so, kk, cb = bev.pack(designs)
    return eval_core(bev.static, bev.arrays, si, so, kk, cb,
                     single_partition, xp=np, seg=NumpySegments, **kw)


def _assert_bitwise(a, b):
    assert a.keys() == b.keys()
    for key in a:
        x, y = np.asarray(a[key]), np.asarray(b[key])
        assert x.dtype == y.dtype and x.shape == y.shape, key
        assert x.tobytes() == y.tobytes(), key


@pytest.mark.parametrize("exec_model", ["streaming", "spmd"])
@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("shape", [TRAIN, PREFILL, DECODE],
                         ids=lambda s: s.mode)
def test_single_partition_path_is_bitwise_the_general_path(
        shape, backend, exec_model):
    """On a cut-free batch the numpy engine's one-partition shortcut
    (``single_partition=True``, what ``evaluate_batch`` takes for such a
    batch) gives the same bits as the general segmented path."""
    prob = _problem("jamba-1.5-large-398b", shape, backend=backend,
                    exec_model=exec_model)
    designs = [v.with_cuts(()) for v in _random_designs(prob, 40, seed=10)]
    bev = prob.batched()
    _assert_bitwise(_program(bev, designs, True),
                    _program(bev, designs, False))


def test_memoised_realisability_matches_the_cube():
    """The per-triple test the numpy engine takes for fold menus too large
    for a cube gives the cube's answers, on every triple over the menu and
    values off it (3, 5) or past it (32), and so the same evaluation."""
    prob = _problem("tinyllama-1.1b", TRAIN)
    bev = prob.batched()
    vals = sorted(set(PLAT.fold_values()) | {3, 5, 32})
    grid = np.array(list(itertools.product(vals, repeat=3)), np.int64)
    si, so, kk = (grid[:, [i]] for i in range(3))
    cube = _realizable(bev.static, bev.arrays, si, so, kk, xp=np)
    assert cube.any() and not cube.all()
    np.testing.assert_array_equal(bev._realizable(si, so, kk), cube)
    designs = _random_designs(prob, 40, seed=11)
    _assert_bitwise(_program(bev, designs, False),
                    _program(bev, designs, False,
                             realizable=bev._realizable))


def test_batched_flags_illegal_cut():
    """A cut off the layer boundary is infeasible in both paths."""
    prob = _problem("tinyllama-1.1b", TRAIN)
    g = prob.graph
    illegal = next(e for e in range(len(g.nodes) - 1)
                   if e not in g.cut_edges)
    v = prob.backend.initial(g).with_cuts((illegal,))
    res = prob.evaluate_many([v])
    assert not res.feasible[0]
    assert not prob.evaluate(v).feasible


def test_pack_unpack_roundtrip():
    prob = _problem("tinyllama-1.1b", TRAIN)
    designs = _random_designs(prob, 10, seed=5)
    be = prob.batched()
    si, so, kk, cb = be.pack(designs)
    for r, v in enumerate(designs):
        assert be.unpack_row(si, so, kk, cb, r) == v


def test_batched_eval_counts_points():
    prob = _problem("tinyllama-1.1b", TRAIN)
    designs = _random_designs(prob, 17, seed=6)
    before = prob.evals_done
    prob.evaluate_many(designs)
    assert prob.evals_done == before + 17


# ----------------------------------------------------------------------
# optimisers on top of the batched engine
# ----------------------------------------------------------------------

def test_brute_force_batched_equals_scalar_engine():
    """The chunked batched enumeration visits the identical design set and
    returns the identical optimum (same Variables) as the scalar engine."""
    for backend in ("simple", "megatron"):
        for include_cuts in (False, True):
            a = brute_force(_problem("tinyllama-1.1b", TRAIN,
                                     backend=backend),
                            include_cuts=include_cuts, engine="scalar")
            b = brute_force(_problem("tinyllama-1.1b", TRAIN,
                                     backend=backend),
                            include_cuts=include_cuts, engine="batched",
                            batch_size=256)
            assert a.points == b.points
            assert a.variables == b.variables
            assert a.evaluation.objective == pytest.approx(
                b.evaluation.objective, rel=1e-9)


def test_brute_force_batched_respects_max_points():
    res = brute_force(_problem("tinyllama-1.1b", TRAIN, backend="spmd"),
                      max_points=100, engine="batched", batch_size=64)
    assert res.points == 100


def test_multichain_annealing_deterministic_and_feasible():
    """chains=K parallel tempering: fixed seed => identical design; result
    is feasible; different seeds explore."""
    kw = dict(max_iters=400, chains=6)
    r1 = simulated_annealing(_problem("tinyllama-1.1b", TRAIN), seed=7, **kw)
    r2 = simulated_annealing(_problem("tinyllama-1.1b", TRAIN), seed=7, **kw)
    r3 = simulated_annealing(_problem("tinyllama-1.1b", TRAIN), seed=8, **kw)
    assert r1.variables == r2.variables
    assert r1.history == r2.history
    assert r1.evaluation.feasible and r3.evaluation.feasible
    assert r1.points >= 400                      # K evals per sweep


def test_single_chain_annealing_unchanged_by_chains_param():
    """chains=1 routes to the scalar path: same seed, same design as a
    plain call (the pre-refactor contract)."""
    a = simulated_annealing(_problem("tinyllama-1.1b", TRAIN), seed=3,
                            max_iters=300)
    b = simulated_annealing(_problem("tinyllama-1.1b", TRAIN), seed=3,
                            max_iters=300, chains=1)
    assert a.variables == b.variables
    assert a.history == b.history
