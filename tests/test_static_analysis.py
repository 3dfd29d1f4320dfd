"""The static-analysis subsystem, tested on planted violations.

Each rule must fire EXACTLY once on its planted fixture (no double
counting, no bleed into sibling rules) and not at all on the sanctioned
idioms or on the real tree — the analyzer gates CI, so a false positive
here is a broken build for everyone.

The AST and recompile front-ends (plus the driver gate) run in the no-jax
matrix too; jaxpr-audit tests skip without jax.
"""
import ast
import dataclasses
import json
import os
import sys
import textwrap

import pytest

from repro.analysis import Report, RuleReport, Violation, load_baseline
from repro.analysis import ast_rules, recompile_lint
from repro.core import accel
from repro.core.accel import EngineUnavailable, jax_available
from repro.core.accel.lowering import StaticSpec, build_static_spec

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_static  # noqa: E402

needs_jax = pytest.mark.skipif(not jax_available(), reason="requires jax")

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


# ----------------------------------------------------------------------
# report plumbing
# ----------------------------------------------------------------------

def test_violation_key_is_line_free():
    v = Violation("ast/eager-jax-import", "src/repro/core/x.py",
                  "msg", line=17)
    assert v.key == "ast/eager-jax-import::src/repro/core/x.py"
    assert "17" in v.format() and "msg" in v.format()


def test_report_json_new_and_fixed_against_baseline():
    v = Violation("r/a", "here", "m")
    rep = Report(mode="nojax", rules=[RuleReport("r/a", [v], 0.5),
                                      RuleReport("r/b", [], 0.1)])
    data = rep.to_json({"r/a::there": "accepted long ago"})
    assert data["new"] == ["r/a::here"]
    assert data["fixed"] == ["r/a::there"]
    assert data["rules"]["r/a"] == {"violations": 1, "seconds": 0.5}


def test_load_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(str(tmp_path / "nope.json")) == {}
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"accepted": {"r::w": "why"}}))
    assert load_baseline(str(p)) == {"r::w": "why"}


# ----------------------------------------------------------------------
# AST pack on planted fixtures
# ----------------------------------------------------------------------

def _tree(src):
    return ast.parse(textwrap.dedent(src))


def test_eager_jax_import_fires_exactly_once():
    src = """
        import numpy as np
        import jax.numpy as jnp

        def fine():
            import jax
            return jax
    """
    vs = ast_rules.check_eager_jax_import(_tree(src), "repro/core/bad.py")
    assert len(vs) == 1
    assert vs[0].rule == "ast/eager-jax-import"
    assert vs[0].where == "src/repro/core/bad.py"
    assert "jax.numpy" in vs[0].message


def test_eager_jax_import_sanctioned_idioms_are_clean():
    src = """
        from typing import TYPE_CHECKING
        if TYPE_CHECKING:
            import jax
        try:
            import jax.numpy as jnp
        except ImportError:
            jnp = None
    """
    assert ast_rules.check_eager_jax_import(
        _tree(src), "repro/core/good.py") == []
    # modules outside the no-jax matrix may import eagerly
    src2 = "import jax\n"
    assert ast_rules.check_eager_jax_import(
        ast.parse(src2), "repro/models/layers.py") == []
    assert ast_rules.check_eager_jax_import(
        ast.parse(src2), "repro/core/accel/eval_jax.py") == []


def test_traced_python_branch_fires_exactly_once():
    src = """
        import functools, jax

        @functools.partial(jax.jit, static_argnums=(0,))
        def f(static, x):
            if x > 0:
                return x
            return -x
    """
    vs = ast_rules.check_traced_python_branch(
        _tree(src), "repro/core/accel/bad.py")
    assert len(vs) == 1
    assert vs[0].rule == "ast/traced-python-branch"
    assert vs[0].where == "src/repro/core/accel/bad.py:f"
    assert "x" in vs[0].message


def test_traced_python_branch_covers_the_array_program():
    """The array program the numpy engine shares with the jitted programs
    is traced code: the rule reads ``core/array_program.py`` as it reads
    ``core/accel/``, and nothing else in ``core/``."""
    src = """
        def eval_core(static, A, si, so, kk, cb, single_partition=False,
                      *, xp, seg, realizable=None):
            if single_partition and realizable is None:
                return A
            if cb.any():
                return si
            return so
    """
    vs = ast_rules.check_traced_python_branch(
        _tree(src), "repro/core/array_program.py")
    assert [(v.where, v.line) for v in vs] == [
        ("src/repro/core/array_program.py:eval_core", 6)]
    assert "cb" in vs[0].message
    assert ast_rules.check_traced_python_branch(
        _tree(src), "repro/core/batched_eval.py") == []
    # and it imports no jax: the no-jax matrix holds it
    assert len(ast_rules.check_eager_jax_import(
        ast.parse("import jax\n"), "repro/core/array_program.py")) == 1


def test_traced_python_branch_static_args_are_legal():
    src = """
        import functools, jax

        @functools.partial(jax.jit, static_argnums=(0, 1))
        def f(static, flag, x):
            if flag:
                return float(static.n_nodes) + x
            return x
    """
    assert ast_rules.check_traced_python_branch(
        _tree(src), "repro/core/accel/good.py") == []
    # outside core/accel/ the rule does not apply at all
    src2 = """
        import jax

        @jax.jit
        def f(x):
            return x if x else -x
    """
    assert ast_rules.check_traced_python_branch(
        _tree(src2), "repro/models/layers.py") == []


def test_unseeded_random_fires_exactly_once():
    src = """
        import numpy as np
        import random

        def test_something():
            rng = np.random.default_rng(0)
            r = random.Random(7)
            return np.random.rand(3), rng.normal(), r.random()
    """
    vs = ast_rules.check_unseeded_random(_tree(src), "tests/test_x.py")
    assert len(vs) == 1
    assert vs[0].rule == "ast/unseeded-random"
    assert "np.random.rand" in vs[0].message


def test_ast_pack_clean_on_real_tree():
    out = ast_rules.run(REPO_ROOT)
    assert {k: v for k, v in out.items() if v} == {}


def test_ast_pack_catches_planted_file_in_checkout(tmp_path):
    """End-to-end over a fake checkout: a planted eager import is found
    by ``run`` with the repo-relative path in the finding."""
    mod = tmp_path / "src" / "repro" / "core"
    mod.mkdir(parents=True)
    (mod / "planted.py").write_text("import jax\n")
    out = ast_rules.run(str(tmp_path))
    keys = [v.key for v in out["ast/eager-jax-import"]]
    assert keys == ["ast/eager-jax-import::src/repro/core/planted.py"]


def test_service_package_in_no_jax_matrix():
    """The mapping service must import (and serve host-engine requests)
    without jax, so an eager jax import there is a lint violation."""
    assert "repro/service/" in ast_rules.NO_JAX_PREFIXES
    vs = ast_rules.check_eager_jax_import(_tree("import jax"),
                                          "repro/service/planted.py")
    assert [v.rule for v in vs] == ["ast/eager-jax-import"]


def test_service_package_scanned_for_unseeded_random(tmp_path):
    """``run`` covers repro/service with the seeded-randomness rule: the
    deterministic threaded service tests must not depend on draws from
    global random state anywhere in the serving stack."""
    mod = tmp_path / "src" / "repro" / "service"
    mod.mkdir(parents=True)
    (mod / "planted.py").write_text(
        "import random\nrandom.shuffle([1, 2])\n")
    out = ast_rules.run(str(tmp_path))
    keys = [v.key for v in out["ast/unseeded-random"]]
    assert keys == ["ast/unseeded-random::src/repro/service/planted.py"]


# ----------------------------------------------------------------------
# recompile lint
# ----------------------------------------------------------------------

def _example_spec():
    return build_static_spec(recompile_lint.example_grid()[0].batched())


def test_recompile_lint_clean_on_example_grid():
    out = recompile_lint.run()
    assert {k: v for k, v in out.items() if v} == {}


def test_spec_varies_fires_exactly_once_per_field():
    spec = _example_spec()
    drifted = dataclasses.replace(spec, mxu_efficiency=0.123)
    vs = recompile_lint.lint_specs({"a/p1/latency": spec,
                                    "b/p2/latency": drifted})
    assert len(vs) == 1
    assert vs[0].rule == "recompile/spec-varies"
    assert vs[0].where == "StaticSpec.mxu_efficiency"
    assert "DeviceArrays" in vs[0].message


def test_spec_field_type_flags_structured_values():
    spec = _example_spec()
    assert recompile_lint.lint_field_types(spec) == []
    bad = dataclasses.replace(spec, mode=("train", "decode"))
    vs = recompile_lint.lint_field_types(bad)
    assert len(vs) == 1
    assert vs[0].where == "StaticSpec.mode"
    assert "tuple" in vs[0].message


def test_build_static_spec_matches_lower_program():
    """The audited spec and the spec that keys the executable cache must
    be the same object-by-value — lower_program routes through
    build_static_spec, so checking one problem locks the contract."""
    if not jax_available():
        pytest.skip("lower_program requires jax")
    from repro.core.accel.lowering import lower_program
    p = recompile_lint.example_grid()[0]
    bev = p.batched()
    static, _ = lower_program(bev)
    assert static == build_static_spec(bev)


# ----------------------------------------------------------------------
# jaxpr audit on planted programs
# ----------------------------------------------------------------------

@needs_jax
def test_host_callback_fires_exactly_once():
    import jax

    def f(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    closed = jax.make_jaxpr(f)(1.0)
    from repro.analysis.jaxpr_audit import audit_jaxpr
    vs = audit_jaxpr(closed, "planted")
    assert [v.rule for v in vs] == ["jaxpr/host-callback"]
    assert vs[0].where == "entry:planted"
    assert "debug_print" in vs[0].message      # jax.debug.print's primitive


@needs_jax
def test_host_callback_found_inside_jitted_body():
    """The walker must recurse into jit sub-jaxprs: the callback hides
    one level down when the planted function is jitted."""
    import jax

    @jax.jit
    def f(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    closed = jax.make_jaxpr(f)(1.0)
    assert closed.jaxpr.eqns[0].primitive.name == "jit"  # it IS nested
    from repro.analysis.jaxpr_audit import audit_jaxpr
    vs = audit_jaxpr(closed, "planted_jit")
    assert [v.rule for v in vs] == ["jaxpr/host-callback"]


@needs_jax
def test_unbounded_while_fires_unless_allowed():
    import jax
    from jax import lax

    def f(x):
        return lax.while_loop(lambda v: v < 100.0, lambda v: v * 2, x)

    closed = jax.make_jaxpr(f)(1.0)
    from repro.analysis.jaxpr_audit import audit_jaxpr
    vs = audit_jaxpr(closed, "planted")
    assert [v.rule for v in vs] == ["jaxpr/unbounded-while"]
    assert audit_jaxpr(closed, "planted", allow_while=True) == []


@needs_jax
def test_dtype_drift_fires_exactly_once():
    import jax
    import jax.numpy as jnp

    def f(x):
        y = x.astype(jnp.float32)          # the silent downcast
        return (y * 2).astype(x.dtype)

    closed = jax.make_jaxpr(f)(jax.numpy.ones(4, jnp.float64) if
                               jax.config.jax_enable_x64 else
                               jax.numpy.ones(4))
    from repro.analysis.jaxpr_audit import audit_jaxpr
    import numpy as np
    expect = np.dtype(np.float64) if jax.config.jax_enable_x64 \
        else np.dtype(np.float32)
    if not jax.config.jax_enable_x64:
        # under x32 the planted cast is a no-op; drift the other way
        def f(x):                                          # noqa: F811
            return x.astype(jax.numpy.float16) * 2

        closed = jax.make_jaxpr(f)(jax.numpy.ones(4))
    vs = audit_jaxpr(closed, "planted", expect_float=expect)
    assert [v.rule for v in vs] == ["jaxpr/dtype-drift"]
    assert "float" in vs[0].message


@needs_jax
def test_batched_gather_fires_on_large_vmapped_gather():
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import (
        GATHER_SIZE_THRESHOLD,
        audit_jaxpr,
    )

    side = int(GATHER_SIZE_THRESHOLD ** 0.5) + 1

    def one(T, idx):
        return jnp.take_along_axis(T, idx, axis=1)

    T = jnp.ones((4, side, side))
    idx = jnp.zeros((4, side, side), jnp.int32)
    big = jax.make_jaxpr(jax.vmap(one))(T, idx)
    vs = audit_jaxpr(big, "planted", vmapped=True)
    assert [v.rule for v in vs] == ["jaxpr/batched-gather"]
    # the unbatched (flattened-index) form of the same gather is clean
    flat = jax.make_jaxpr(one)(
        jnp.ones((4 * side, side)), jnp.zeros((4 * side, side), jnp.int32))
    assert audit_jaxpr(flat, "planted", vmapped=True) == []
    # and a small vmapped gather (sweep-body menu draw) is exempt
    small = jax.make_jaxpr(jax.vmap(one))(
        jnp.ones((4, 3, 5)), jnp.zeros((4, 3, 5), jnp.int32))
    assert audit_jaxpr(small, "planted", vmapped=True) == []


@pytest.mark.slow
@needs_jax
def test_every_engine_entry_point_audits_clean():
    from repro.analysis import jaxpr_audit
    timings = {}
    out = jaxpr_audit.run(timings=timings)
    assert {k: v for k, v in out.items() if v} == {}
    # every registered entry point was actually lowered
    assert sorted(timings) == sorted(
        f"lower:{ep.name}" for ep in jaxpr_audit.build_entry_points())


# ----------------------------------------------------------------------
# driver gate
# ----------------------------------------------------------------------

def test_driver_clean_tree_exits_zero(tmp_path, monkeypatch):
    out = tmp_path / "report.json"
    rc = check_static.main(["--mode", "nojax", "--fail-on-new",
                            "--json", str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    assert data["mode"] == "nojax"
    assert data["new"] == [] and data["violations"] == []
    assert all(r["seconds"] >= 0 for r in data["rules"].values())


def test_driver_fails_nonzero_naming_rule_and_location(
        tmp_path, monkeypatch, capsys):
    planted = Violation("ast/eager-jax-import",
                        "src/repro/core/planted.py", "planted import")

    def fake_run(root):
        return {"ast/eager-jax-import": [planted]}

    monkeypatch.setattr(ast_rules, "run", fake_run)
    rc = check_static.main(["--mode", "nojax", "--fail-on-new"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "ast/eager-jax-import::src/repro/core/planted.py" in err

    # the same violation accepted in a baseline passes the gate
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(
        {"accepted": {planted.key: "known, tracked elsewhere"}}))
    rc = check_static.main(["--mode", "nojax", "--fail-on-new",
                            "--baseline", str(bl)])
    assert rc == 0


def test_driver_write_baseline_roundtrip(tmp_path, monkeypatch):
    planted = Violation("recompile/spec-varies", "StaticSpec.mode", "m")
    monkeypatch.setattr(check_static, "run_passes", lambda mode: (
        Report(mode=mode, rules=[RuleReport("recompile/spec-varies",
                                            [planted], 0.0)]), {}))
    bl = tmp_path / "baseline.json"
    rc = check_static.main(["--mode", "nojax", "--write-baseline",
                            "--baseline", str(bl)])
    assert rc == 0
    assert load_baseline(str(bl)) == {planted.key: "m"}
    # with the fresh baseline the gate passes; without it, it fails
    assert check_static.main(["--mode", "nojax", "--fail-on-new",
                              "--baseline", str(bl)]) == 0
    assert check_static.main(["--mode", "nojax", "--fail-on-new",
                              "--baseline", str(tmp_path / "none.json")]) \
        == 1


def test_checked_in_baseline_is_empty():
    """The tree is clean; the shipped baseline must stay empty so any
    regression is a NEW violation, not silently accepted."""
    assert load_baseline(check_static.DEFAULT_BASELINE) == {}


# ----------------------------------------------------------------------
# EngineUnavailable chaining (satellite)
# ----------------------------------------------------------------------

def test_require_jax_chains_the_original_importerror(monkeypatch):
    monkeypatch.delenv("REPRO_NO_JAX", raising=False)
    # None in sys.modules makes ``import jax`` raise ImportError even
    # when jax is installed; when it isn't, the natural failure chains
    monkeypatch.setitem(sys.modules, "jax", None)
    with pytest.raises(EngineUnavailable, match="pip install jax") as ei:
        accel.require_jax()
    assert isinstance(ei.value.__cause__, ImportError)


def test_require_jax_masked_mentions_the_mask(monkeypatch):
    monkeypatch.setenv("REPRO_NO_JAX", "1")
    with pytest.raises(EngineUnavailable, match="REPRO_NO_JAX"):
        accel.require_jax("the fleet sweep")
