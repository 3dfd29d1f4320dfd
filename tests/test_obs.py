"""Telemetry subsystem tests: tracer, metrics registry, TRACE_COUNTS
back-compat shim, run records and the BENCH report tool — plus the
differential guard that turning telemetry ON changes no optimiser's
result (design, objective, points, history) on any engine.

The tracer/metrics/runrecord layers are stdlib-only, so everything here
except the jax-marked differential cases runs in the no-jax CI matrix.
"""
import importlib.util
import json
import os
import time

import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.accel import jax_available
from repro.obs import metrics, runrecord, trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_report():
    path = os.path.join(REPO_ROOT, "tools", "bench_report.py")
    spec = importlib.util.spec_from_file_location("bench_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# tracer
# ----------------------------------------------------------------------

def test_span_nesting_depth_parent_and_order():
    trace.enable()
    with trace.span("outer", kind="o") as outer:
        with trace.span("mid") as mid:
            with trace.span("inner") as inner:
                pass
        with trace.span("mid2"):
            pass
    spans = {s["name"]: s for s in trace.snapshot()}
    assert set(spans) == {"outer", "mid", "inner", "mid2"}
    assert spans["outer"]["depth"] == 0 and spans["outer"]["parent"] == -1
    assert spans["mid"]["depth"] == 1
    assert spans["mid"]["parent"] == spans["outer"]["id"]
    assert spans["inner"]["depth"] == 2
    assert spans["inner"]["parent"] == spans["mid"]["id"]
    assert spans["mid2"]["parent"] == spans["outer"]["id"]
    assert spans["outer"]["attrs"] == {"kind": "o"}
    # completion order: children finish before parents
    order = [s["name"] for s in trace.snapshot()]
    assert order.index("inner") < order.index("mid") < order.index("outer")
    assert outer.id != mid.id != inner.id


def test_span_timing_monotonic():
    trace.enable()
    with trace.span("a"):
        with trace.span("b"):
            time.sleep(0.002)
    a, b = {s["name"]: s for s in trace.snapshot()}["a"], \
           {s["name"]: s for s in trace.snapshot()}["b"]
    assert a["dur_s"] >= b["dur_s"] >= 0.002
    assert a["start_s"] <= b["start_s"]
    assert b["start_s"] + b["dur_s"] <= a["start_s"] + a["dur_s"] + 1e-9
    for s in (a, b):
        assert s["start_s"] >= 0.0         # epoch-relative, post-reset


def test_span_disabled_is_stopwatch_only():
    assert not trace.enabled()
    with trace.span("ghost", x=1) as sp:
        time.sleep(0.001)
    assert sp.elapsed_s() >= 0.001         # timing works with tracing off
    assert sp.set(y=2) is sp               # set() is a no-op, still chains
    assert trace.snapshot() == []          # nothing recorded
    # elapsed_s is live while open
    sp2 = trace.span("open")
    sp2.__enter__()
    t1 = sp2.elapsed_s()
    t2 = sp2.elapsed_s()
    assert t2 >= t1 >= 0.0
    sp2.__exit__(None, None, None)


def test_span_records_failure_and_tolerates_foreign_exit():
    trace.enable()
    with pytest.raises(RuntimeError):
        with trace.span("boom"):
            raise RuntimeError("x")
    boom = [s for s in trace.snapshot() if s["name"] == "boom"]
    assert boom and boom[0]["attrs"].get("failed") is True
    # manually interleaved exits must not corrupt the stack
    a = trace.span("manual_a").__enter__()
    b = trace.span("manual_b").__enter__()
    a.__exit__(None, None, None)           # out of order
    b.__exit__(None, None, None)
    with trace.span("after"):
        pass
    after = [s for s in trace.snapshot() if s["name"] == "after"]
    assert after[0]["depth"] == 0 and after[0]["parent"] == -1


def test_traced_decorator_and_buffer_cap():
    tr = trace.Tracer(max_spans=3)

    @tr.traced("f")
    def f(x):
        return x + 1

    assert f(1) == 2                       # disabled: passthrough
    assert tr.snapshot() == []
    tr.enable()
    for _ in range(5):
        assert f(1) == 2
    assert len(tr.snapshot()) == 3         # capped
    assert tr.dropped() == 2
    tr.reset()
    assert tr.snapshot() == [] and tr.dropped() == 0


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------

def test_registry_instruments_and_snapshot():
    metrics.counter("c").inc()
    metrics.counter("c").inc(4)
    metrics.gauge("g").set(2.5)
    metrics.histogram("h").observe(1.0)
    metrics.histogram("h").observe(3.0)
    metrics.series("s").append(1, 10.0)
    snap = metrics.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 2.5
    assert snap["histograms"]["h"] == {"count": 2, "sum": 4.0, "min": 1.0,
                                       "max": 3.0, "mean": 2.0}
    assert snap["series"]["s"] == {"points": [[1.0, 10.0]], "dropped": 0}
    json.dumps(snap)                       # must be JSON-serialisable


def test_registry_reset_between_tests_fixture():
    # the autouse conftest fixture must have wiped the previous test's
    # instruments before this one started
    snap = metrics.snapshot()
    assert "c" not in snap["counters"]
    assert trace.snapshot() == [] and not trace.enabled()


def test_series_cap():
    s = metrics.Series()
    for i in range(metrics.SERIES_CAP + 7):
        s.append(i, 0.0)
    assert len(s.points) == metrics.SERIES_CAP
    assert s.dropped == 7


def test_trace_counts_shim_back_compat():
    from repro.core.accel.eval_jax import TRACE_COUNTS as TC_EVAL
    from repro.obs.metrics import TRACE_COUNTS, TRACE_KEYS
    assert TC_EVAL is TRACE_COUNTS         # historic import home re-exports
    assert tuple(TRACE_COUNTS) == TRACE_KEYS
    assert len(TRACE_COUNTS) == 11         # 7 engine keys + 4 *_shard (PR 8)
    assert "bf_chunk" in TRACE_COUNTS
    assert TRACE_COUNTS["bf_chunk"] == 0   # re-materialised post-reset
    TRACE_COUNTS["bf_chunk"] += 1          # the jitted-body idiom
    assert TRACE_COUNTS["bf_chunk"] == 1
    assert dict(TRACE_COUNTS)["bf_chunk"] == 1
    # the ledger is backed by registry counters
    assert metrics.snapshot()["counters"]["accel.traces.bf_chunk"] == 1
    with pytest.raises(KeyError):
        TRACE_COUNTS["made_up_key"]
    with pytest.raises(KeyError):
        TRACE_COUNTS["made_up_key"] = 1
    with pytest.raises(TypeError):
        del TRACE_COUNTS["bf_chunk"]
    metrics.reset()                        # keys survive a registry reset
    assert TRACE_COUNTS["bf_chunk"] == 0


def test_device_dispatch_classifies_trace_vs_cache_hit():
    from repro.obs.metrics import TRACE_COUNTS
    trace.enable()
    with metrics.device_dispatch("bf_chunk", bucket=0):
        TRACE_COUNTS["bf_chunk"] += 1      # simulate an XLA trace
    with metrics.device_dispatch("bf_chunk", bucket=0):
        pass                               # simulate a cache hit
    c = metrics.snapshot()["counters"]
    assert c["accel.dispatches.bf_chunk"] == 2
    assert c["accel.dispatches.bf_chunk[0]"] == 2
    assert c["accel.cache_hits.bf_chunk"] == 1
    assert c["accel.cache_hits.bf_chunk[0]"] == 1
    spans = [s for s in trace.snapshot()
             if s["name"] == "accel.dispatch.bf_chunk"]
    assert len(spans) == 2
    assert spans[0]["attrs"].get("traced") is True
    assert "traced" not in spans[1]["attrs"]


def test_note_result():
    from repro.core.optimizers.common import OptimResult
    res = OptimResult(variables=None, evaluation=None, points=100,
                      seconds=0.5, history=[(1, 9.0), (7, 3.0)],
                      name="annealing-jax4")
    metrics.note_result(res, engine="jax")
    snap = metrics.snapshot()
    assert snap["counters"]["optim.annealing[jax].runs"] == 1
    assert snap["counters"]["optim.annealing[jax].points"] == 100
    assert snap["gauges"]["optim.annealing[jax].points_per_s"] == 200.0
    assert snap["series"]["optim.annealing[jax].convergence"]["points"] == \
        [[1.0, 9.0], [7.0, 3.0]]
    assert snap["histograms"]["optim.annealing[jax].seconds"]["count"] == 1


# ----------------------------------------------------------------------
# run records + bench report
# ----------------------------------------------------------------------

def _small_record():
    trace.enable()
    with trace.span("pipeline.optimise_mapping"):
        with trace.span("accel.dispatch.bf_chunk"):
            pass
    metrics.counter("optim.brute_force[jax].points").inc(12)
    metrics.gauge("optim.brute_force[jax].points_per_s").set(48.0)
    trace.disable()
    return runrecord.capture("unit", config={"smoke": True})


def test_runrecord_roundtrip_and_diff(tmp_path):
    rec = _small_record()
    assert runrecord.validate(rec) == []
    assert rec["git_sha"] != ""
    assert rec["platform"]["python"]
    path = str(tmp_path / "rr.jsonl")
    assert runrecord.append(rec, path) == path
    rec2 = dict(rec, created_unix=rec["created_unix"] + 1)
    runrecord.append(rec2, path)
    loaded = runrecord.load(path)
    assert len(loaded) == 2
    assert loaded[0] == json.loads(json.dumps(rec))   # JSON round-trip
    assert runrecord.latest(path, "unit")["created_unix"] == \
        rec2["created_unix"]
    assert runrecord.latest(path, "other_lane") is None
    totals = runrecord.span_totals(loaded[0])
    assert totals["pipeline.optimise_mapping"]["count"] == 1
    d = runrecord.diff(loaded[0], loaded[1])
    assert d["lanes"] == ["unit", "unit"]
    assert d["counters"]["optim.brute_force[jax].points"]["delta"] == 0
    assert d["gauges"]["optim.brute_force[jax].points_per_s"]["ratio"] == 1.0
    assert d["span_totals_s"]["pipeline.optimise_mapping"]["ratio"] > 0


def test_runrecord_rejects_invalid(tmp_path):
    assert runrecord.validate({"schema": 1}) != []
    assert runrecord.validate("not a dict") != []
    bad = _small_record()
    bad["metrics"] = "nope"
    with pytest.raises(ValueError):
        runrecord.append(bad, str(tmp_path / "x.jsonl"))
    p = tmp_path / "corrupt.jsonl"
    p.write_text("{not json}\n")
    with pytest.raises(ValueError):
        runrecord.load(str(p))


def test_bench_report_row_emit_and_cli(tmp_path, capsys):
    br = _bench_report()
    rec = _small_record()
    row = br.bench_row(rec)
    assert row["lane"] == "unit"
    assert row["points_per_s"] == {"brute_force[jax]": 48.0}
    assert row["points"]["brute_force[jax].points"] == 12
    assert "pipeline.optimise_mapping" in row["span_totals_s"]
    assert row["config"] == {"smoke": True}
    out = br.write_bench(rec, str(tmp_path))
    assert out.endswith("BENCH_unit.json")
    assert json.load(open(out)) == json.loads(json.dumps(row))

    records = str(tmp_path / "rr.jsonl")
    runrecord.append(rec, records)
    assert br.main(["validate", records]) == 0
    assert br.main(["validate", records, "--lane", "nope"]) == 1
    assert br.main(["emit", records, "--lane", "unit",
                    "--out", str(tmp_path)]) == 0
    assert br.main(["diff", records, records, "--lane", "unit",
                    "--out", str(tmp_path / "d.json")]) == 0
    assert "counters" in json.load(open(tmp_path / "d.json"))
    capsys.readouterr()


# ----------------------------------------------------------------------
# the differential guard: telemetry must not change results
# ----------------------------------------------------------------------

def _result_tuple(r):
    return (r.variables, r.points, r.history, r.evaluation.objective,
            r.evaluation.feasible)


@given(data=st.data())
@settings(max_examples=2, deadline=None)
def test_telemetry_does_not_change_results(data):
    """Enabling spans + metrics is observation-only: every optimiser on
    every engine returns the bit-identical design, objective, points and
    history with telemetry on as with it off."""
    from test_random_differential import _fresh, problems
    from repro.core.optimizers import (brute_force, rule_based,
                                       simulated_annealing)

    prob = data.draw(problems())
    engines = ["scalar", "numpy"] + (["jax"] if jax_available() else [])
    runs = [
        ("bf", lambda e: brute_force(_fresh(prob), engine=e,
                                     include_cuts=False, max_points=300,
                                     batch_size=64)),
        ("sa", lambda e: simulated_annealing(_fresh(prob), engine=e,
                                             seed=11, max_iters=30)),
        ("rb", lambda e: rule_based(_fresh(prob), engine=e)),
    ]
    for eng in engines:
        for label, run in runs:
            trace.disable()
            trace.reset()
            metrics.reset()
            off = run(eng)
            trace.reset()
            metrics.reset()
            trace.enable()
            on = run(eng)
            trace.disable()
            assert _result_tuple(off) == _result_tuple(on), (label, eng)
            # and telemetry actually observed the run
            snap = metrics.snapshot()
            assert any(k.startswith("optim.") and k.endswith(".runs")
                       for k in snap["counters"]), (label, eng)
            names = {s["name"] for s in trace.snapshot()}
            assert SPAN_SITES.get((label, eng), set()) <= names, (label, eng)


#: spans each (optimiser, engine) run of the differential must record
SPAN_SITES = {
    ("rb", "scalar"): {"optim.rb.host", "optim.repair"},
    ("rb", "numpy"): {"optim.rb.host", "optim.repair"},
    ("rb", "jax"): {"optim.rb.host", "optim.repair", "accel.build_sa_tables",
                    "accel.h2d.rb_descend", "accel.dispatch.rb_descend",
                    "accel.d2h.rb_descend"},
    ("sa", "jax"): {"optim.repair", "accel.build_sa_tables",
                    "accel.h2d.sa_state", "accel.d2h.sa_sweeps",
                    "accel.d2h.sa_best"},
}


@pytest.mark.skipif(not jax_available(), reason="jax engines absent")
def test_telemetry_differential_fleet(tiny_problem):
    """The fleet runners too: telemetry-on == telemetry-off, and the
    per-bucket dispatch/cache-hit ledger is populated."""
    from repro.core.accel.fleet import fleet_brute_force

    kw = dict(include_cuts=False, max_points=2000, batch_size=256)
    probs = [tiny_problem]
    trace.disable()
    off = fleet_brute_force(probs, **kw)
    trace.reset()
    metrics.reset()
    trace.enable()
    on = fleet_brute_force(probs, **kw)
    trace.disable()
    assert [_result_tuple(a) for a in off] == [_result_tuple(b) for b in on]
    snap = metrics.snapshot()
    assert snap["counters"]["accel.dispatches.fleet_bf_chunk"] >= 1
    assert "accel.dispatches.fleet_bf_chunk[0]" in snap["counters"]
    names = {s["name"] for s in trace.snapshot()}
    assert {"fleet.bucketing", "fleet.bf.bucket",
            "accel.dispatch.fleet_bf_chunk"} <= names


@pytest.mark.skipif(not jax_available(), reason="jax engines absent")
def test_instrumented_pipeline_produces_valid_record(tiny_arch,
                                                     small_platform):
    """End-to-end: optimise_mapping under telemetry yields a run record
    that validates, round-trips, and carries the span taxonomy the BENCH
    row quotes (lowering, dispatch, d2h, pipeline stages)."""
    from repro.core.pipeline import optimise_mapping
    from conftest import TINY_SHAPE

    trace.enable()
    optimise_mapping(tiny_arch, TINY_SHAPE, platform=small_platform,
                     optimiser="brute_force", engine="jax",
                     max_points=2000, batch_size=512)
    trace.disable()
    rec = runrecord.capture("pipe", config={})
    assert runrecord.validate(rec) == []
    names = {s["name"] for s in rec["spans"]}
    assert {"pipeline.optimise_mapping", "pipeline.make_problem",
            "pipeline.optimise", "pipeline.export_plan",
            "optim.brute_force.jax", "accel.dispatch.bf_chunk",
            "accel.d2h.bf_chunk", "accel.build_static_spec",
            "accel.lower_program"} <= names
    c = rec["metrics"]["counters"]
    assert c["optim.brute_force[jax].runs"] == 1
    assert c["accel.dispatches.bf_chunk"] >= 1
    row = _bench_report().bench_row(rec)
    assert "brute_force[jax]" in row["points_per_s"]
    assert row["dispatches"]["bf_chunk"] >= 1
