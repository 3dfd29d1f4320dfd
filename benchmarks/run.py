"""Benchmark harness: one module per paper table/figure + the roofline
report, plus a ``tests`` lane running the tier-1 suite with per-test
timings and engine lanes for the accelerated search.

    python -m benchmarks.run [names...] [--smoke] [--hetero]

``--smoke`` shrinks the smoke-capable lanes (``accel``, ``fleet``,
``shard``, ``serve``) to their smallest spaces for CI: the accel smoke lane runs the
smallest Table-IV space, asserts the jax==numpy optimum agreement, and
fails if it exceeds 60 s. ``--hetero`` switches the ``fleet`` lane to the
heterogeneous-platform grid (networks x platforms as ONE fleet program;
see benchmarks/fleet_sweep.py and docs/benchmarks.md). The ``shard`` lane
(benchmarks/shard_sweep.py) times the sharded engines across a device
grid — run it under ``REPRO_FAKE_DEVICES=8`` for the full curve
(``runtime_config.apply_env()`` below consumes the variable before any
jax backend init).

Every lane runs with telemetry enabled (``repro/obs``): on completion a
run record — spans, metrics, config, git SHA, platform fingerprint — is
appended to ``experiments/benchmarks/runrecords.jsonl`` and distilled
into ``BENCH_<lane>.json`` via ``tools/bench_report.py``
(``docs/observability.md`` documents the schema and how to read a row)."""
from __future__ import annotations

import os
import subprocess
import sys
import time

from repro import runtime_config

# Runtime knobs (REPRO_FAKE_DEVICES et al.) must land before anything can
# initialise a jax backend — the shard lane's device grid depends on it.
runtime_config.apply_env()
runtime_config.compilation_cache()

from repro.obs import metrics, runrecord, trace  # noqa: E402

from benchmarks import (  # noqa: E402
    comap_bench,
    fig2_optimizer_compare,
    fig4_batch_partitions,
    fleet_sweep,
    roofline,
    serve_bench,
    shard_sweep,
    table4_design_space,
    table5_objectives,
    table6_vs_baseline,
)
from benchmarks.common import RESULT_DIR

def run_tests():
    """Test lane: the tier-1 suite with the 25 slowest tests reported
    (the randomized differential suite's generator budgets are reviewed
    through this listing — a slow random-graph strategy shows up here).

    The suite runs on the CPU backend: this process may already hold the
    accelerator (an earlier lane of the same invocation), and a chip
    belongs to one process at a time, so a child reaching for it would
    fail or hang."""
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", "--durations=25"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        check=False).returncode


ALL = {
    "table4": table4_design_space.run,
    "fig2": fig2_optimizer_compare.run,
    "table5": table5_objectives.run,
    "table6": table6_vs_baseline.run,
    "fig4": fig4_batch_partitions.run,
    "roofline": roofline.run,
    "accel": table4_design_space.run_accel,
    "fleet": fleet_sweep.run,
    "shard": shard_sweep.run,
    "serve": serve_bench.run,
    "comap": comap_bench.run,
    "tests": run_tests,
}

#: lanes that run only when asked for explicitly
_ON_DEMAND = ("tests", "accel", "fleet", "shard", "serve", "comap")

#: lanes accepting the ``--smoke`` flag
_SMOKEABLE = ("accel", "fleet", "shard", "serve", "comap")


def _bench_report():
    """``tools/bench_report.py`` as a module (tools/ is not a package)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "bench_report.py")
    spec = importlib.util.spec_from_file_location("bench_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _emit_record(lane: str, config: dict) -> None:
    """Capture this lane's telemetry into the JSONL trajectory and the
    flat ``BENCH_<lane>.json`` row. Never aborts a finished lane."""
    try:
        record = runrecord.capture(lane, config=config)
        path = runrecord.append(
            record, os.path.join(RESULT_DIR, "runrecords.jsonl"))
        bench = _bench_report().write_bench(record, RESULT_DIR)
        print(f"[{lane}] run record -> {path}; bench row -> {bench}",
              flush=True)
    except Exception as err:                     # pragma: no cover
        print(f"[{lane}] run record FAILED: {err}", flush=True)


def main(argv=None) -> int:
    argv = list(argv if argv is not None else sys.argv[1:])
    smoke = "--smoke" in argv
    hetero = "--hetero" in argv
    while "--smoke" in argv:
        argv.remove("--smoke")
    while "--hetero" in argv:
        argv.remove("--hetero")
    names = argv or [n for n in ALL if n not in _ON_DEMAND]
    for name in names:
        if name not in ALL:
            print(f"unknown benchmark {name!r}; known: {sorted(ALL)}")
            return 1
        t0 = time.time()
        kwargs = {"smoke": True} if smoke and name in _SMOKEABLE else {}
        if hetero and name == "fleet":
            kwargs["hetero"] = True
        lane = "fleet_hetero" if (hetero and name == "fleet") else name
        trace.reset()
        metrics.reset()
        trace.enable()
        try:
            ret = ALL[name](**kwargs)
        finally:
            trace.disable()
        _emit_record(lane, {"lane": name, "smoke": smoke,
                            "hetero": hetero and name == "fleet"})
        print(f"[{name}] done in {time.time()-t0:.1f}s", flush=True)
        if isinstance(ret, int) and ret != 0:
            return ret                    # tests lane: propagate pytest's rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
