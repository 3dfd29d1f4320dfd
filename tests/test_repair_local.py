"""``repair`` scores each fold move on the nodes it changes; a full
``check`` and ``evaluate`` of every candidate must give the same answer.

``_full_repair`` is the loop as it was written before the partition-local
scoring: every candidate is rescored with a whole-design float64
``check`` and ``evaluate``. The differential test runs both on random
start designs (random folds and cuts, merged designs that are not
``propagate`` fixed points, the rule-based uniform seeds) for every
backend and three small graphs, and requires the same design and the
same number of candidates. The cost-shape test holds ``repair`` to a
constant number of memo misses however many candidates it scores.

``Backend.set_fold_local`` builds a fold move of a ``propagate`` fixed
point where it reaches; the local-move tests hold it to ``set_fold`` and
its moved nodes to the whole-design diff for every node, variable and
menu value, and the rule-based seeds to the ones ``set_fold`` builds.
"""
import random

import pytest

from repro.configs import get_arch, reduced
from repro.core.backends import BACKENDS, VARS
from repro.core.graph_builder import build_hdgraph
from repro.core.hdgraph import Variables, partitions_from_cuts
from repro.core.objectives import Problem
from repro.core.optimizers.common import repair
from repro.core.optimizers.rule_based import _seeded_candidates
from repro.core.platform import Platform
from repro.obs import metrics

from conftest import TINY_DECODE, TINY_SHAPE

GRAPHS = {
    "dense": ("tinyllama-1.1b", TINY_SHAPE),
    "hybrid": ("jamba-1.5-large-398b", TINY_SHAPE),
    "latent_moe": ("kimi-k2-1t-a32b", TINY_DECODE),
}


def _full_repair(problem, v, max_steps=1024):
    """The whole-design rescoring ``repair``; returns (design, candidates)."""
    graph, backend, platform = problem.graph, problem.backend, problem.platform
    candidates = 0

    def part_residency(vv):
        evals = problem.evaluate(vv).node_evals
        parts = partitions_from_cuts(graph, vv.cuts)
        res = [sum(evals[i].hbm_resident for i in p) for p in parts]
        worst = max(range(len(parts)), key=lambda pi: res[pi])
        return parts, res, worst, evals

    def structural(vv):
        return sum(1 for msg in problem.check(vv).violations
                   if not msg.startswith("partition"))

    base_structural = structural(v)
    for _ in range(max_steps):
        if problem.check(v).ok:
            return v, candidates
        parts, res, wi, evals = part_residency(v)
        worst = parts[wi]
        worst_res = res[wi]
        order = sorted(worst, key=lambda i: -evals[i].hbm_resident)
        best = None
        for i in order:
            for var in ("s_out", "kern", "s_in"):
                cands = backend.candidates(graph, i, var, platform)
                cur = getattr(v, var)[i]
                higher = [c for c in cands if c > cur]
                if not higher:
                    continue
                v2 = backend.set_fold(graph, v, i, var, higher[0])
                candidates += 1
                if structural(v2) > base_structural:
                    continue
                parts2, res2, _, _ = part_residency(v2)
                pi2 = next(p for p in range(len(parts2))
                           if worst[0] in parts2[p])
                if res2[pi2] < worst_res - 1e-9:
                    if best is None or res2[pi2] < best[0]:
                        best = (res2[pi2], v2)
            if best is not None:
                break
        if best is not None:
            v = best[1]
            continue
        edges = [e for e in graph.cut_edges if e not in v.cuts]
        inner = [e for e in edges if worst[0] <= e < worst[-1]]
        if not inner:
            return v, candidates
        v = v.with_cuts(tuple(sorted(set(v.cuts) | {inner[len(inner) // 2]})))
    return v, candidates


def _graph(name):
    arch, shape = GRAPHS[name]
    return build_hdgraph(reduced(get_arch(arch)), shape)


def _problem(graph, backend, exec_model, hbm_bytes, mesh):
    plat = Platform(name="test", mesh_axes=mesh, hbm_bytes=hbm_bytes)
    return Problem(graph=graph, platform=plat, backend=BACKENDS[backend],
                   objective="latency", exec_model=exec_model)


def _random_folds(rng, prob):
    g, b, plat = prob.graph, prob.backend, prob.platform
    n = len(g.nodes)
    folds = {var: tuple(rng.choice(b.candidates(g, i, var, plat))
                        for i in range(n)) for var in VARS}
    return Variables((), folds["s_in"], folds["s_out"], folds["kern"])


def _random_cuts(rng, graph, share):
    return tuple(e for e in graph.cut_edges if rng.random() < share)


def _starts(rng, prob):
    """Start designs of every kind ``repair`` is handed."""
    g, b = prob.graph, prob.backend
    out = [b.initial(g)]
    # random folds and cuts, as drawn and as propagated
    v = _random_folds(rng, prob).with_cuts(_random_cuts(rng, g, 0.5))
    out += [v, b.propagate(g, v)]
    # a merged design: a propagated design with a cut removed, not
    # propagated again (not a fixed point of propagate)
    v = b.propagate(g, _random_folds(rng, prob).with_cuts(
        _random_cuts(rng, g, 0.7)))
    if v.cuts:
        out.append(v.with_cuts(c for c in v.cuts
                               if c != rng.choice(v.cuts)))
    # a rule-based uniform seed: no cuts, one triple set node by node
    trip = [rng.choice(prob.platform.fold_values()) for _ in VARS]
    v = Variables((), (1,) * len(g.nodes), (1,) * len(g.nodes),
                  (1,) * len(g.nodes))
    for j in range(len(g.nodes)):
        for var, val in zip(VARS, trip):
            v = b.set_fold(g, v, j, var, val)
    out.append(v)
    return out


@pytest.mark.parametrize("graph_name", sorted(GRAPHS))
@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_local_scoring_matches_full_rescoring(graph_name, backend):
    graph = _graph(graph_name)
    rng = random.Random(f"{graph_name}/{backend}")
    fat = None
    n_cases = 0
    for trial in range(6):
        mesh = ((("data", 4), ("model", 4)) if trial % 3
                else (("data", 2), ("model", 4)))
        exec_model = ("streaming", "spmd")[trial % 2]
        if fat is None:
            probe = _problem(graph, backend, exec_model, 2**40, mesh)
            fat = max(e.hbm_resident for e in
                      probe.evaluate(probe.backend.initial(graph)).node_evals)
        # from a budget no fold can meet to one every design meets
        hbm = fat * rng.choice([0.01, 0.1, 0.3, 0.6, 1.2, 4.0])
        shape_prob = _problem(graph, backend, exec_model, hbm, mesh)
        for v0 in _starts(rng, shape_prob):
            old_prob = _problem(graph, backend, exec_model, hbm, mesh)
            new_prob = _problem(graph, backend, exec_model, hbm, mesh)
            want, cands = _full_repair(old_prob, v0)
            metrics.reset()
            got = repair(new_prob, v0)
            counted = metrics.snapshot()["counters"].get(
                "optim.repair.candidates", 0)
            assert got == want, (trial, v0)
            assert counted == cands, (trial, v0)
            assert new_prob.check(got).ok == old_prob.check(want).ok
            n_cases += 1
    assert n_cases >= 24


def test_repair_costs_a_constant_number_of_memo_misses():
    """The hybrid graph's initial design takes many fold moves to repair;
    the whole-design rescoring paid one memo miss per candidate."""
    graph = _graph("hybrid")
    mesh = (("data", 4), ("model", 4))
    probe = _problem(graph, "spmd", "spmd", 2**40, mesh)
    v0 = probe.backend.initial(graph)
    fat = max(e.hbm_resident for e in probe.evaluate(v0).node_evals)
    old_prob = _problem(graph, "spmd", "spmd", fat * 0.1, mesh)
    new_prob = _problem(graph, "spmd", "spmd", fat * 0.1, mesh)
    want, cands = _full_repair(old_prob, v0)
    assert cands >= 20 and old_prob.host_evals >= cands // 2
    v = repair(new_prob, v0)
    assert v == want
    assert new_prob.host_evals <= 3
    assert metrics.snapshot()["counters"]["optim.repair.candidates"] == cands


MESHES = {"4x4": (("data", 4), ("model", 4)), "2x4": (("data", 2), ("model", 4))}


def _fixed_points(rng, prob, count):
    """Random designs with random cuts, propagated: fixed points."""
    g, b = prob.graph, prob.backend
    out = []
    for share in (0.0, 0.3, 0.7)[:count]:
        v = _random_folds(rng, prob).with_cuts(_random_cuts(rng, g, share))
        out.append(b.propagate(g, v))
    return out


@pytest.mark.parametrize("mesh", sorted(MESHES))
@pytest.mark.parametrize("graph_name", sorted(GRAPHS))
@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_local_move_matches_set_fold(mesh, graph_name, backend):
    graph = _graph(graph_name)
    prob = _problem(graph, backend, "spmd", 2**40, MESHES[mesh])
    b, plat = prob.backend, prob.platform
    n = len(graph.nodes)
    assert b.keeps_fixed_points(graph)
    rng = random.Random(f"{mesh}/{graph_name}/{backend}")
    moves = 0
    for v in _fixed_points(rng, prob, 3):
        assert b.propagate(graph, v) == v
        before = list(zip(v.s_in, v.s_out, v.kern))
        for i in range(n):
            for var in VARS:
                for value in b.candidates(graph, i, var, plat):
                    want = b.set_fold(graph, v, i, var, value)
                    got, moved = b.set_fold_local(graph, v, i, var, value)
                    after = list(zip(want.s_in, want.s_out, want.kern))
                    diff = [j for j in range(n) if after[j] != before[j]]
                    assert got == want, (i, var, value)
                    assert moved == diff, (i, var, value)
                    # what chains of local moves rely on
                    assert b.propagate(graph, want) == want
                    moves += 1
    assert moves > 3 * n


def _sequential_seeds(prob):
    """The rule-based uniform seeds, in their order, built by ``set_fold``
    node by node and repaired."""
    graph, b, plat = prob.graph, prob.backend, prob.platform
    n = len(graph.nodes)
    out = []
    values = plat.fold_values()
    for trip in ((si, so, kk) for si in values for so in values
                 for kk in values):
        chips = trip[0] * trip[1] * trip[2]
        if chips > plat.chips or not plat.folds_realizable(trip) or \
                chips < plat.chips // 4:
            continue
        v = Variables((), (1,) * n, (1,) * n, (1,) * n)
        for j in range(n):
            for var, val in zip(VARS, trip):
                v = b.set_fold(graph, v, j, var, val)
        out.append(repair(prob, v))
    return out


@pytest.mark.parametrize("graph_name", sorted(GRAPHS))
@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_seeds_match_the_sequential_set_fold(graph_name, backend):
    graph = _graph(graph_name)
    prob = _problem(graph, backend, "spmd", 2**40, MESHES["4x4"])
    b, plat = prob.backend, prob.platform
    n = len(graph.nodes)
    seeds = _seeded_candidates(prob)
    want = _sequential_seeds(prob)
    assert len(seeds) == len(want) > 0
    assert seeds == want


def test_keeps_fixed_points_guards_a_mixed_scan_group():
    """A scan group whose first member alone is elementwise: ``set_fold``
    can leave a design that ``propagate`` moves again, and a local move of
    such a design can miss the re-tie. ``keeps_fixed_points`` says so, and
    ``repair`` and the seeds still give what ``set_fold`` gives."""
    import dataclasses

    graph = _graph("dense")
    group = next(m for m in graph.scan_groups().values() if len(m) > 1)
    other = next(j for j, nd in enumerate(graph.nodes)
                 if nd.scan_group >= 0 and j not in group)
    graph.nodes = [dataclasses.replace(nd, elementwise=True)
                   if j == group[0] else nd
                   for j, nd in enumerate(graph.nodes)]
    mesh = MESHES["4x4"]
    for backend in sorted(BACKENDS):
        b = BACKENDS[backend]
        assert not b.keeps_fixed_points(graph)
        if not b.intra_matching:
            continue
        v0 = b.initial(graph).with_cuts(())
        v1 = b.set_fold(graph, v0, group[0], "s_in", 2)
        assert b.propagate(graph, v1) != v1
        if not b.inter_matching:         # else it re-ties the partition
            assert b.set_fold_local(graph, v1, other, "s_out", 2)[0] != \
                b.set_fold(graph, v1, other, "s_out", 2)
        probe = _problem(graph, backend, "spmd", 2**40, mesh)
        fat = max(e.hbm_resident for e in
                  probe.evaluate(b.initial(graph)).node_evals)
        for share in (0.1, 0.3, 0.6):
            for v in (b.initial(graph), v0):
                want, cands = _full_repair(
                    _problem(graph, backend, "spmd", fat * share, mesh), v)
                metrics.reset()
                got = repair(_problem(graph, backend, "spmd", fat * share,
                                      mesh), v)
                assert got == want, (backend, share)
                assert metrics.snapshot()["counters"].get(
                    "optim.repair.candidates", 0) == cands
        prob = _problem(graph, backend, "spmd", fat * 0.3, mesh)
        assert _seeded_candidates(prob) == _sequential_seeds(prob)
