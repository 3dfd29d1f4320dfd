#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator chips of this machine.

    python3 bench/run.py --workload stablelm-3b.rb --seed 7 --seconds 10 \\
        --trace 0

Everything about a cell comes from files found by name: the cell in
``BENCHMARK.json``; its configuration in ``bench/configs/<config>.json``;
its traffic mix in ``bench/traffic/<traffic>.json``, which names the
request loop (``bench/loops/<loop>.py``) and the correctness check
(``bench/checks/<check>.py``); and every metric in
``bench/metrics/<metric>.py``. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from the
program's counters and spans and a profiler trace of the window.

The run refuses to start (exit 2, no result) without the program's
sources beside it, and (exit 3, no result) when JAX finds no TPU or fewer
chips than the cell asks for. Otherwise it warms up every shape the mix
uses, measures for ``--seconds``, checks every answer of the window
against the plain reference in ``bench/reference`` and prints, as the
last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, (with ``--trace 1``)
``breakdown``, and ``checks``: each number compared with its limit.
Those numbers are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import program as prog_door     # noqa: E402

#: jax.monitoring events that make up compilation (trace, lower, compile)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: the profiler annotation around the traced part of the window
WINDOW_NOTE = "bench.window"


class SetupError(RuntimeError):
    """The run cannot start: a file is missing or the chips are not there."""

    def __init__(self, msg: str, code: int):
        super().__init__(msg)
        self.code = code


class CompileClock:
    """Compile seconds and backend compiles, from jax's monitoring events
    (the listener lives for the process)."""

    def __init__(self) -> None:
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.compile_s += duration
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def reading(self):
        return (self.compile_s, self.compiles)


# ----------------------------------------------------------------------
# files, found by name
# ----------------------------------------------------------------------

def _json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as err:
        raise SetupError(f"cannot read {os.path.relpath(path, ROOT)}: {err}",
                         2) from err


def load_cell(name: str) -> dict:
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json", 2)
    cell = cells[name]

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": _json(os.path.join(BENCH, "configs",
                                     cell["config"] + ".json")),
        "traffic": _json(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def module(kind: str, name: str):
    return importlib.import_module(f"{kind}.{name}")


# ----------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------

def chips(cell: dict):
    """The devices the cell asks for, or ``SetupError``: no fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"no TPU: jax found {devs[0].platform!r}", 3)
    if len(devs) < cell["chips"]:
        raise SetupError(f"the cell needs {cell['chips']} chips; jax sees "
                         f"{len(devs)}", 3)
    return devs[:cell["chips"]]


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


class Run:
    """What the metric readers read: one run's counts, clocks, spans and
    (with ``--trace 1``) the reduced device trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def measure(spec: dict, seed: int, seconds: float, traced: bool,
            devs, clock: CompileClock) -> dict:
    """Set up, warm up, run the window; with ``traced``, profile the
    window's first round and read the per-layer record from it."""
    import jax
    config, traffic = spec["config"], spec["traffic"]
    loop = module("loops", traffic["loop"])
    program = prog_door.Program(config, traffic)

    loop.warm_up(program, traffic, seed)
    setup_compile = clock.reading()
    record = {}
    trace_dir = None
    hooks = {}
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        window_note = jax.profiler.TraceAnnotation(WINDOW_NOTE)

        def first_round(answers):
            if record:
                return
            window_note.__exit__(None, None, None)
            jax.profiler.stop_trace()
            program.spans_on(False)
            done = [a for a in answers if "plan" in a]
            record.update(designs=len(done),
                          points=sum(a["points"] for a in done),
                          counters=_delta(c0, program.counters()),
                          spans=program.spans())

        hooks = {"annotate": jax.profiler.TraceAnnotation,
                 "on_round": first_round}
    c0 = program.counters()
    if traced:
        program.spans_on(True)
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_opts())
        window_note.__enter__()
    t_window = time.perf_counter()
    win = loop.window(program, traffic, seed, seconds, **hooks)
    window_compile = clock.reading()
    peak = peak_bytes(devs)
    answers = win["answers"]
    done = [a for a in answers if "plan" in a]
    run = Run(
        setup_s=t_window - T_START, window_s=win["window_s"],
        designs=len(done), attempted=len(answers), failed=win["failed"],
        setup_compile_s=setup_compile[0],
        compiles_in_window=window_compile[1] - setup_compile[1],
        compile_s_in_window=window_compile[0] - setup_compile[0],
        traced=None)
    if traced:
        import shutil

        import trace_reduce
        try:
            record["trace"] = trace_reduce.reduce_dir(
                trace_dir, [d.id for d in devs], spans=record["spans"])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.traced = record
    return {"run": run, "answers": answers, "peak": peak,
            "traffic": traffic}


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _profile_opts():
    """Device events and the benchmark's own annotations; no Python
    function tracing (it would dwarf the device events)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def metric_values(run: Run, metrics) -> dict:
    out = {}
    for m in metrics:
        value = module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def verdict(answers, config: dict, traffic: dict) -> dict:
    check = module("checks", traffic["check"])
    values = check.check(answers, config, traffic)
    return {name: {"value": float(v), "limit": float(traffic["limits"][name])}
            for name, v in values.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_cell(args.workload)
        if not prog_door.importable():
            raise SetupError("the program's sources (src/repro) are not "
                             "beside the benchmark", 2)
        cache = prog_door.compilation_cache()
        devs = chips(spec["cell"])
    except SetupError as err:
        print(f"bench: {err}", file=sys.stderr)
        return err.code
    dev0 = devs[0]
    print(f"[device] platform={dev0.platform} kind={dev0.device_kind} "
          f"count={len(devs)} compile_cache={cache}", flush=True)
    clock = CompileClock()
    out = measure(spec, args.seed, args.seconds, bool(args.trace), devs,
                  clock)
    run = out["run"]
    print(f"[window] designs={run.designs} window_s={run.window_s!r} "
          f"setup_s={run.setup_s!r} "
          f"compiles_in_window={run.compiles_in_window} "
          f"compile_s_in_window={run.compile_s_in_window!r} "
          f"setup_compile_s={run.setup_compile_s!r}", flush=True)
    metrics = spec["per_layer"] if args.trace else spec["end_to_end"]
    values = metric_values(run, metrics)

    checks = verdict(out["answers"], spec["config"], out["traffic"])
    correct = run.failed == 0 and run.designs > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs), "memory_peak_bytes": out["peak"]}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": values, "device": device}
    if run.traced is not None:
        device["busy_s"] = run.traced["trace"]["busy_s"]
        device["window_s"] = run.traced["trace"]["window_s"]
        result["breakdown"] = run.traced["trace"]["breakdown"]
    result["checks"] = {k: {"value": _num(c["value"]), "limit": c["limit"]}
                        for k, c in checks.items()}
    for a in out["answers"]:
        if "error" in a:
            print(f"request {a['index']} failed: {a['error'][-400:]}",
                  file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} = {_num(c['value'])!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def _num(x: float):
    """JSON has no infinity: an infinite reading prints as a string."""
    return x if math.isfinite(x) else "inf"


if __name__ == "__main__":
    sys.exit(main())
