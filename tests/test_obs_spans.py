"""Spans inside the optimisers' host loops and the device searches'
set-up, the ``optim.host_evals`` counter, the mirror of every recorded
span onto the profiler's clock, and the benchmark readers of them.

On the CPU with a tiny problem: the jax rule-based and annealing runs
record the span tree ``docs/observability.md`` lists; each recorded span
appears once, under its own name, in a profiler trace taken while tracing
is on; with tracing off no profiler annotation is made.
"""
import os
import sys

import pytest

from repro.core.accel import jax_available
from repro.core.hdgraph import Variables
from repro.obs import metrics, trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO_ROOT, "bench")

needs_jax = pytest.mark.skipif(not jax_available(),
                               reason="jax engines absent")


def _map(tiny_arch, small_platform, optimiser, **kw):
    from conftest import TINY_SHAPE
    from repro.core.pipeline import optimise_mapping
    return optimise_mapping(tiny_arch, TINY_SHAPE, platform=small_platform,
                            optimiser=optimiser, exec_model="spmd", **kw)


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


@needs_jax
def test_rule_based_jax_records_the_host_loop_tree(tiny_arch,
                                                   small_platform):
    trace.enable()
    _map(tiny_arch, small_platform, "rule_based", engine="jax")
    trace.disable()
    spans = trace.snapshot()
    named = _by_name(spans)
    by_id = {s["id"]: s for s in spans}
    for name in ("optim.rb.host", "optim.repair", "accel.build_sa_tables",
                 "accel.h2d.rb_descend", "accel.dispatch.rb_descend",
                 "accel.d2h.rb_descend"):
        assert name in named, name
    dispatches = metrics.snapshot()["counters"]["accel.dispatches.rb_descend"]
    assert len(named["accel.d2h.rb_descend"]) == dispatches
    assert len(named["accel.h2d.rb_descend"]) == dispatches
    # Algorithm 2 runs on the host between descents: every repair is
    # inside one stretch of it
    for s in named["optim.repair"]:
        assert by_id[s["parent"]]["name"] == "optim.rb.host"
    # the copies happen before the enqueue, not inside it
    for s in named["accel.dispatch.rb_descend"]:
        assert not [c for c in spans if c["parent"] == s["id"]]
    h2d = sorted(named["accel.h2d.rb_descend"], key=lambda s: s["start_s"])
    enq = sorted(named["accel.dispatch.rb_descend"],
                 key=lambda s: s["start_s"])
    d2h = sorted(named["accel.d2h.rb_descend"], key=lambda s: s["start_s"])
    for a, b, c in zip(h2d, enq, d2h):
        assert a["start_s"] + a["dur_s"] <= b["start_s"]
        assert b["start_s"] + b["dur_s"] <= c["start_s"]


@needs_jax
def test_annealing_jax_records_set_up_and_readback(tiny_arch,
                                                   small_platform):
    trace.enable()
    _map(tiny_arch, small_platform, "annealing", engine="jax", chains=4,
         max_iters=64, seed=3)
    trace.disable()
    named = _by_name(trace.snapshot())
    for name in ("accel.build_sa_tables", "optim.repair",
                 "accel.h2d.sa_state", "accel.dispatch.sa_sweeps",
                 "accel.d2h.sa_sweeps", "accel.d2h.sa_best"):
        assert name in named, name
    assert len(named["accel.d2h.sa_sweeps"]) == \
        metrics.snapshot()["counters"]["accel.dispatches.sa_sweeps"]


@pytest.mark.parametrize("optimiser,kw", [
    ("rule_based", {"engine": "numpy"}),
    ("annealing", {"engine": "numpy", "max_iters": 200, "seed": 1}),
])
def test_host_evals_counts_memo_misses(tiny_arch, small_platform,
                                       monkeypatch, optimiser, kw):
    """``optim.host_evals`` is the request's float64 evaluations that
    missed the memo: one memo entry each, batched points not counted."""
    from repro.core import pipeline
    made = []
    real = pipeline.make_problem

    def spy(*a, **k):
        made.append(real(*a, **k))
        return made[-1]

    monkeypatch.setattr(pipeline, "make_problem", spy)
    _map(tiny_arch, small_platform, optimiser, **kw)
    _map(tiny_arch, small_platform, optimiser, **kw)
    misses = [sum(1 for k in p._cache if isinstance(k, Variables))
              for p in made]
    assert all(m > 0 for m in misses)
    assert [p.host_evals for p in made] == misses
    assert metrics.snapshot()["counters"]["optim.host_evals"] == sum(misses)


def _spy_fold_moves(monkeypatch, backend):
    """Record each call of ``set_fold`` and of ``set_fold_local``."""
    calls = []
    for name in ("set_fold", "set_fold_local"):
        real = getattr(type(backend), name)

        def spy(self, *a, _real=real, _name=name, **kw):
            calls.append(_name)
            return _real(self, *a, **kw)

        monkeypatch.setattr(type(backend), name, spy)
    return calls


def test_repair_candidates_counts_every_fold_move(tiny_arch, monkeypatch):
    """``optim.repair.candidates`` grows by one for each fold move that
    ``repair`` builds (by ``set_fold`` or ``set_fold_local``), and not for
    a design already feasible."""
    from conftest import TINY_SHAPE
    from repro.core.backends import BACKENDS
    from repro.core.graph_builder import build_hdgraph
    from repro.core.objectives import Problem
    from repro.core.optimizers.common import repair
    from repro.core.platform import Platform

    graph = build_hdgraph(tiny_arch, TINY_SHAPE)
    backend = BACKENDS["spmd"]
    calls = _spy_fold_moves(monkeypatch, backend)
    v0 = backend.initial(graph)
    roomy = Problem(graph=graph, platform=Platform(
        name="roomy", mesh_axes=(("data", 4), ("model", 4))),
        backend=backend, objective="latency", exec_model="spmd")
    fat = max(e.hbm_resident for e in roomy.evaluate(v0).node_evals)
    assert repair(roomy, v0) == v0
    assert "optim.repair.candidates" not in metrics.snapshot()["counters"]
    tight = Problem(graph=graph, platform=Platform(
        name="tight", mesh_axes=(("data", 4), ("model", 4)),
        hbm_bytes=fat * 0.3), backend=backend, objective="latency",
        exec_model="spmd")
    repair(tight, v0)
    assert len(calls) > 0
    assert metrics.snapshot()["counters"]["optim.repair.candidates"] == \
        len(calls)


@pytest.mark.parametrize("backend_name", ["spmd", "megatron", "simple"])
def test_repair_local_candidates_count_the_local_moves(tiny_arch,
                                                       monkeypatch,
                                                       backend_name):
    """``optim.repair.local_candidates`` counts the candidates built by
    ``set_fold_local``: all of them from a ``propagate`` fixed point, fewer
    from a merged design that is not one, none where ``repair`` has
    nothing to do."""
    from conftest import TINY_SHAPE
    from repro.core.backends import BACKENDS
    from repro.core.graph_builder import build_hdgraph
    from repro.core.objectives import Problem
    from repro.core.optimizers.common import repair
    from repro.core.platform import Platform

    graph = build_hdgraph(tiny_arch, TINY_SHAPE)
    backend = BACKENDS[backend_name]
    mesh = (("data", 4), ("model", 4))

    def problem(hbm_bytes):
        return Problem(graph=graph, platform=Platform(
            name="t", mesh_axes=mesh, hbm_bytes=hbm_bytes), backend=backend,
            objective="latency", exec_model="spmd")

    def counters():
        return metrics.snapshot()["counters"]

    v0 = backend.initial(graph)
    assert backend.propagate(graph, v0) == v0
    fat = max(e.hbm_resident
              for e in problem(2**50).evaluate(v0).node_evals)
    metrics.reset()
    assert repair(problem(2**50), v0) == v0
    assert "optim.repair.local_candidates" not in counters()

    calls = _spy_fold_moves(monkeypatch, backend)
    metrics.reset()
    repair(problem(fat * 0.3), v0)
    got = counters()
    assert got["optim.repair.candidates"] > 0
    assert got["optim.repair.local_candidates"] == \
        got["optim.repair.candidates"] == calls.count("set_fold_local")

    # the graph merged into one partition, one scan group left untied:
    # not a fixed point until the first accepted move re-ties it
    second = next(m for m in graph.scan_groups().values() if len(m) > 1)[1]
    k = max(backend.candidates(graph, second, "kern", problem(1).platform))
    merged = v0.with_cuts(()).replace_node(second, kern=k)
    assert backend.propagate(graph, merged) != merged
    del calls[:]
    metrics.reset()
    repair(problem(fat * 0.3), merged)
    got = counters()
    assert calls[0] == "set_fold"
    assert got.get("optim.repair.local_candidates", 0) < \
        got["optim.repair.candidates"] == len(calls)


# ----------------------------------------------------------------------
# the mirror onto the profiler's clock
# ----------------------------------------------------------------------

class _Counting:
    """A stand-in for ``jax.profiler.TraceAnnotation`` that counts."""
    made = []

    def __init__(self, name):
        self.name = name
        self.open = False
        _Counting.made.append(self)

    def __enter__(self):
        self.open = True

    def __exit__(self, *exc):
        self.open = False


def test_annotation_made_only_while_recording(monkeypatch):
    _Counting.made = []
    monkeypatch.setattr(trace, "_profiler_annotation", lambda: _Counting)
    with trace.span("a"):
        with trace.span("b"):
            pass
    assert _Counting.made == []                # never enabled
    trace.enable()
    trace.disable()
    with trace.span("a"):
        pass
    assert _Counting.made == []                # enabled, then disabled
    trace.enable()
    with trace.span("a") as outer:
        with trace.span("b"):
            assert [n.open for n in _Counting.made] == [True, True]
        assert outer.elapsed_s() >= 0.0
    trace.disable()
    assert [n.name for n in _Counting.made] == ["a", "b"]
    assert not any(n.open for n in _Counting.made)
    assert [s["name"] for s in trace.snapshot()] == ["b", "a"]


@needs_jax
def test_disabled_run_makes_no_annotation(tiny_arch, small_platform,
                                          monkeypatch):
    import jax.profiler
    made = []

    class Spy(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    _map(tiny_arch, small_platform, "rule_based", engine="jax")
    assert made == []
    trace.enable()
    _map(tiny_arch, small_platform, "rule_based", engine="jax")
    trace.disable()
    assert sorted(made) == sorted(s["name"] for s in trace.snapshot())


def test_no_mirror_without_jax(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    trace.enable()
    with trace.span("a") as sp:
        assert sp._note is None
    trace.disable()
    assert [s["name"] for s in trace.snapshot()] == ["a"]


def _bench_module(monkeypatch, name):
    import importlib
    monkeypatch.syspath_prepend(BENCH)
    return importlib.import_module(name)


@needs_jax
def test_every_span_lands_on_the_profiler_clock(tiny_arch, small_platform,
                                                tmp_path, monkeypatch):
    """Each recorded span is one host-plane event of its own name in a
    profile taken with tracing on, and the benchmark's request-pairing
    offset (``trace_reduce._offset``) places it within 1 ms of it."""
    import jax
    trace_reduce = _bench_module(monkeypatch, "trace_reduce")
    _map(tiny_arch, small_platform, "rule_based", engine="jax")  # compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    trace.reset()
    trace.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation(trace_reduce.REQUEST):
                _map(tiny_arch, small_platform, "rule_based", engine="jax")
    finally:
        jax.profiler.stop_trace()
        trace.disable()
    spans = trace.snapshot()
    path = trace_reduce.find_trace(str(tmp_path))
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(e.start_ns * 1e-9)
    named = _by_name(spans)
    assert len(named["accel.d2h.rb_descend"]) >= 2
    offset = trace_reduce._offset(trace_reduce.load(path)["annotations"],
                                  spans)
    assert offset is not None
    worst = 0.0
    for name, mine in named.items():
        seen = sorted(events.get(name, []))
        assert len(seen) == len(mine), name
        for s, t in zip(sorted(x["start_s"] for x in mine), seen):
            worst = max(worst, abs(s + offset - t))
    print(f"largest offset error {worst!r} s over {len(spans)} spans")
    assert worst < 1e-3


# ----------------------------------------------------------------------
# the benchmark's readers of the new spans and counter
# ----------------------------------------------------------------------

def _span(sid, name, dur, parent=-1):
    return {"name": name, "start_s": 0.0, "dur_s": dur, "depth": 0,
            "id": sid, "parent": parent, "thread": 0, "attrs": {}}


ROUND = {
    "designs": 4, "points": 100, "trace": None,
    "counters": {"optim.host_evals": 600, "accel.dispatches.rb_descend": 8,
                 "graph.nodes.mla": 244, "optim.repair.candidates": 1000},
    "spans": [
        _span(0, "optim.rb.host", 0.010),
        _span(1, "optim.repair", 0.004, parent=0),
        _span(2, "optim.repair", 0.001, parent=1),
        _span(3, "optim.rb.host", 0.030),
        _span(4, "accel.h2d.rb_descend", 0.002),
        _span(5, "accel.h2d.sa_state", 0.006),
        _span(6, "accel.d2h.rb_descend", 0.100),
        _span(7, "accel.d2h.sa_sweeps", 0.200),
        _span(8, "accel.d2h.sa_best", 0.004),
        _span(9, "accel.build_sa_tables", 0.020),
        _span(10, "accel.build_sa_tables", 0.012),
        _span(11, "accel.dispatch.rb_descend", 0.5),
        _span(12, "graph.build", 0.008),
    ],
}


@pytest.mark.parametrize("name,expected", [
    ("host_merge_ms_per_design", 10.0),
    ("repair_ms_per_design", 1.0),
    ("host_evals_per_design", 150.0),
    ("h2d_ms_per_design", 2.0),
    ("readback_ms_per_design", 76.0),
    ("tables_ms_per_design", 8.0),
    ("parse_ms_per_design", 2.0),
    ("latent_nodes_per_design", 61.0),
    ("repair_candidates_per_design", 250.0),
])
def test_reader_on_a_hand_built_round(monkeypatch, name, expected):
    reader = _bench_module(monkeypatch, f"metrics.{name}")
    run = type("Run", (), {})()
    run.traced = ROUND
    assert reader.read(run) == pytest.approx(expected, rel=1e-12)
    # a round without the spans and counters (a program without them)
    run.traced = dict(ROUND, spans=[_span(0, "pipeline.optimise", 1.0)],
                      counters={})
    assert reader.read(run) is None
    run.traced = None
    assert reader.read(run) is None
