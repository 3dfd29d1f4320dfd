#!/usr/bin/env python
"""Static-analysis gate: run the ``repro.analysis`` front-ends, emit a
JSON report, and compare it against the checked-in baseline.

    python tools/check_static.py                    # report only
    python tools/check_static.py --fail-on-new      # the CI gate
    python tools/check_static.py --mode nojax       # force the jax-free
                                                    # front-ends (what the
                                                    # no-jax CI cell runs)
    python tools/check_static.py --write-baseline   # accept current state

Modes:
  auto   (default) jax front-end included iff jax imports and is not
         masked by ``REPRO_NO_JAX``.
  jax    require the jaxpr audit; exit 2 if jax is unavailable. x64 is
         enabled first so the audit checks the strict float64
         differential regime.
  nojax  AST pack + recompile lint only (sets ``REPRO_NO_JAX=1`` for
         the passes so an installed jax cannot leak in) — runnable with
         nothing but the standard library + numpy.

Both settings hold only while the passes run and are restored after, so
``main`` can be called inside a longer-lived process (the test suite).

Exit status: 0 clean (or report-only), 1 new violations with
``--fail-on-new`` (each printed with its rule id and location), 2 usage /
environment error.

Baseline workflow (``tools/static_baseline.json``): a violation that is
deliberate ships as ``"rule::where": "justification"`` under ``accepted``;
``--fail-on-new`` then ignores it while still failing on anything else.
Keys are line-free (see ``repro.analysis.Violation.key``) so entries
survive unrelated edits. ``--write-baseline`` regenerates the file from
the current tree — review the diff before committing it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

DEFAULT_BASELINE = os.path.join(ROOT, "tools", "static_baseline.json")


def resolve_mode(mode: str) -> str:
    if mode == "nojax":
        return "nojax"
    from repro.core.accel import jax_available
    if mode == "jax":
        if not jax_available():
            print("check_static: --mode jax but jax is unavailable "
                  "(not installed, or masked by REPRO_NO_JAX)",
                  file=sys.stderr)
            raise SystemExit(2)
        return "jax"
    return "jax" if jax_available() else "nojax"


@contextlib.contextmanager
def mode_settings(mode: str):
    """``REPRO_NO_JAX=1`` (nojax) or ``jax_enable_x64`` (jax) for the
    duration of the block; the previous value is restored on exit."""
    if mode == "jax":
        import jax
        with jax.enable_x64(True):
            yield
        return
    old = os.environ.get("REPRO_NO_JAX")
    os.environ["REPRO_NO_JAX"] = "1"
    try:
        yield
    finally:
        if old is None:
            del os.environ["REPRO_NO_JAX"]
        else:
            os.environ["REPRO_NO_JAX"] = old


def run_passes(mode: str):
    from repro.analysis import Report, RuleReport

    report = Report(mode=mode)
    lower_timings = {}

    def add_pass(out, seconds):
        # rules inside one front-end share a single pass over the tree /
        # grid / jaxprs; each carries that pass's wall time
        for rule, violations in out.items():
            report.rules.append(RuleReport(rule, violations, seconds))

    from repro.analysis import ast_rules
    t0 = time.perf_counter()
    add_pass(ast_rules.run(ROOT), time.perf_counter() - t0)

    from repro.analysis import recompile_lint
    t0 = time.perf_counter()
    add_pass(recompile_lint.run(), time.perf_counter() - t0)

    if mode == "jax":
        from repro.analysis import jaxpr_audit
        t0 = time.perf_counter()
        add_pass(jaxpr_audit.run(timings=lower_timings),
                 time.perf_counter() - t0)

    return report, lower_timings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("auto", "jax", "nojax"),
                    default="auto")
    ap.add_argument("--json", metavar="PATH",
                    help="write the machine-readable report here")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--fail-on-new", action="store_true",
                    help="exit 1 on any violation not in the baseline")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept the current tree's violations")
    args = ap.parse_args(argv)

    mode = resolve_mode(args.mode)
    from repro.analysis import load_baseline

    with mode_settings(mode):
        report, lower_timings = run_passes(mode)
    baseline = load_baseline(args.baseline)
    data = report.to_json(baseline)
    data["lowerings"] = {k: round(v, 4)
                         for k, v in sorted(lower_timings.items())}

    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(data, f, indent=2, sort_keys=True)
            f.write("\n")

    print(f"check_static [{mode}]: "
          f"{len(report.rules)} rules, {len(report.violations)} "
          f"violation(s), {len(data['new'])} new, "
          f"{len(data['fixed'])} fixed-in-baseline")
    for r in sorted(report.rules, key=lambda r: -r.seconds):
        print(f"  {r.seconds:8.3f}s  {r.rule:28s} "
              f"{len(r.violations)} finding(s)")
    for v in report.violations:
        marker = "baseline" if v.key in baseline else "NEW"
        print(f"  [{marker}] {v.format()}")

    if args.write_baseline:
        accepted = {v.key: v.message for v in report.violations}
        with open(args.baseline, "w", encoding="utf-8") as f:
            json.dump({"accepted": accepted}, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(accepted)} accepted key(s) to {args.baseline}")
        return 0

    if args.fail_on_new and data["new"]:
        print(f"check_static: {len(data['new'])} new violation(s):",
              file=sys.stderr)
        for key in data["new"]:
            print(f"  {key}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
