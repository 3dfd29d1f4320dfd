#!/usr/bin/env python3
"""Drive the mapping optimiser's main path once on a TPU and check it.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path, on four chips

One chip: tinyllama-1.1b at its registered (full) config, ``train_4k`` on
``V5E_POD`` under the spmd execution model, through the entry points a
user calls:

  mapping    ``optimise_mapping(engine="jax")`` with rule_based,
             brute_force and annealing. Rule-based and brute-force must
             return the numpy engine's design; annealing (its own device
             random stream) must return a feasible design whose device
             objective matches the float64 scalar evaluation to 1e-5
             relative, and the same design for the same seed.
  portfolio  ``optimise_portfolio`` over three registered archs (one
             vmapped fleet program per bucket) must equal the
             per-problem jax loop.
  served     a ``MappingServer`` behind ``serve_http`` on 127.0.0.1
             answers ``POST /v1/mapping`` requests with ``engine: jax``;
             every answer must equal the direct call, and a repeated
             request must come from the solved-design cache.

``--chips 4`` runs only the sharded engines: brute force with
``devices=4`` and a four-arch ``optimise_portfolio(devices=4)``, each
compared with the one-device run.

Every phase prints its wall time with compilation (trace, lowering and
XLA compile, from jax's monitoring events) counted apart, and the
number of engine traces. A check that fails raises; the last line of
standard output, a JSON object naming the device, is printed only when
every phase passed. Without a TPU, or without the repository's
``src/`` next to this file, the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import http.client
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "tinyllama-1.1b"
SHAPE = "train_4k"
#: the streaming model's per-partition chip budget leaves tinyllama-1.1b
#: train_4k on V5E_POD with no design the optimisers reach (every one
#: exceeds 256 chips or 16 GiB somewhere), so annealing's feasibility
#: check would test nothing; under spmd all three return feasible designs
EXEC_MODEL = "spmd"
PORTFOLIO = ("tinyllama-1.1b", "llama3.2-1b", "stablelm-3b")
SHARDED_PORTFOLIO = PORTFOLIO + ("granite-moe-1b-a400m",)
MAX_POINTS = 200_000
CHAINS = 32
SA_RTOL = 1e-5          # float32 device objective vs float64 scalar model

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A phase's result disagrees with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Compile seconds and persistent-cache hits, from jax's monitoring
    events (registered once per process)."""

    def __init__(self) -> None:
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, traced=()):
    """Time one phase; require each entry point in ``traced`` to trace."""
    from repro.core.accel.eval_jax import TRACE_COUNTS
    before = dict(TRACE_COUNTS)
    c0, h0 = clock.compile_s, clock.cache_hits
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    grew = {k: v - before[k] for k, v in TRACE_COUNTS.items()
            if v != before[k]}
    compile_s = clock.compile_s - c0
    print(f"[{name}] wall_s={wall:.3f} compile_s={compile_s:.3f} "
          f"run_s={wall - compile_s:.3f} traces={sum(grew.values())} "
          f"{grew} cache_hits={clock.cache_hits - h0}", flush=True)
    for key in traced:
        check(grew.get(key, 0) > 0,
              f"{name}: jitted entry point {key!r} never traced")


def same_result(a, b, what: str) -> None:
    """Design, point count and improvement history all identical."""
    check(a.variables == b.variables, f"{what}: designs differ")
    check(a.points == b.points, f"{what}: points {a.points} != {b.points}")
    check(a.history == b.history, f"{what}: improvement histories differ")


def _problem(arch_name: str):
    from repro.configs import SHAPES_BY_NAME, get_arch
    from repro.core.pipeline import make_problem
    return make_problem(get_arch(arch_name), SHAPES_BY_NAME[SHAPE],
                        exec_model=EXEC_MODEL)


def _plan(problem, result):
    from repro.core.exporter import export_plan
    return export_plan(problem.graph, result.variables, problem.platform,
                       problem.exec_model, result.evaluation)


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------

def mapping_phase(clock: CompileClock) -> dict:
    """rule_based / brute_force / annealing through optimise_mapping."""
    import jax
    from repro.configs import SHAPES_BY_NAME, get_arch
    from repro.core.accel.eval_jax import JaxEvaluator
    from repro.core.optimizers import OPTIMIZERS
    from repro.core.pipeline import optimise_mapping

    arch, shape = get_arch(ARCH), SHAPES_BY_NAME[SHAPE]
    problem = _problem(ARCH)
    print(f"[problem] {ARCH} {SHAPE}: {len(problem.graph.nodes)} nodes, "
          f"platform {problem.platform.name}", flush=True)
    arrays = JaxEvaluator.from_problem(problem).arrays
    placed = {d for leaf in jax.tree_util.tree_leaves(arrays)
              for d in leaf.devices()}
    check(placed == {jax.devices()[0]},
          f"lowered DeviceArrays live on {placed}, not the default TPU")

    direct = {}
    for optimiser, kw, traced in (
            ("rule_based", {}, ("rb_descend",)),
            ("brute_force", {"max_points": MAX_POINTS}, ("bf_chunk",)),
            ("annealing", {"chains": CHAINS, "seed": 0}, ("sa_sweeps",))):
        with phase(f"mapping.{optimiser}.jax", clock, traced):
            plan = optimise_mapping(arch, shape, optimiser=optimiser,
                                    exec_model=EXEC_MODEL, engine="jax",
                                    **kw)
        with phase(f"mapping.{optimiser}.jax.warm", clock):
            res = OPTIMIZERS[optimiser](problem, engine="jax", **kw)
        check(plan == _plan(problem, res),
              f"{optimiser}: optimise_mapping plan != the optimiser's")
        if optimiser == "annealing":
            with phase("mapping.annealing.jax.repeat", clock):
                again = OPTIMIZERS[optimiser](problem, engine="jax", **kw)
            same_result(res, again, "annealing, same seed")
            ev = res.evaluation
            check(ev.feasible, "annealing returned an infeasible design")
            device_obj = res.history[-1][1]
            rel = abs(device_obj - ev.objective) / abs(ev.objective)
            check(rel <= SA_RTOL,
                  f"annealing: device objective {device_obj!r} vs scalar "
                  f"{ev.objective!r} (relative {rel:.3g} > {SA_RTOL})")
            print(f"[mapping.annealing] objective={ev.objective!r} "
                  f"device={device_obj!r} rel={rel:.3g} "
                  f"points={res.points}", flush=True)
        else:
            with phase(f"mapping.{optimiser}.numpy", clock):
                ref = OPTIMIZERS[optimiser](problem, engine="numpy", **kw)
            check(res.variables == ref.variables,
                  f"{optimiser}: jax design != numpy design")
            check(res.points == ref.points,
                  f"{optimiser}: points {res.points} != {ref.points}")
            print(f"[mapping.{optimiser}] objective="
                  f"{res.evaluation.objective!r} "
                  f"feasible={res.evaluation.feasible} "
                  f"points={res.points} (== numpy engine)", flush=True)
        direct[(ARCH, optimiser)] = (problem, res, kw)
    return direct


def portfolio_phase(clock: CompileClock, direct: dict) -> None:
    """Three archs as one fleet sweep == the per-problem jax loop."""
    from repro.configs import SHAPES_BY_NAME
    from repro.core.optimizers import brute_force
    from repro.core.pipeline import optimise_portfolio

    kw = {"max_points": MAX_POINTS}
    with phase("portfolio.fleet", clock, ("fleet_bf_chunk",)):
        plans = optimise_portfolio(list(PORTFOLIO), SHAPES_BY_NAME[SHAPE],
                                   optimiser="brute_force",
                                   exec_model=EXEC_MODEL, engine="jax",
                                   **kw)
    for name, plan in zip(PORTFOLIO, plans):
        if (name, "brute_force") not in direct:
            problem = _problem(name)
            with phase(f"portfolio.loop.{name}", clock):
                res = brute_force(problem, engine="jax", **kw)
            direct[(name, "brute_force")] = (problem, res, kw)
        problem, res, _ = direct[(name, "brute_force")]
        check(plan == _plan(problem, res),
              f"portfolio: {name} fleet plan != per-problem jax loop")
        print(f"[portfolio] {name}: objective={plan.objective_value!r} "
              f"(== per-problem loop)", flush=True)


def _post(port: int, body: dict) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", "/v1/mapping", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
    finally:
        conn.close()
    check(resp.status == 200, f"POST /v1/mapping -> {resp.status}: "
                              f"{payload}")
    return payload


def served_phase(clock: CompileClock, direct: dict) -> None:
    """Requests over HTTP == the direct calls; a repeat is a cache hit."""
    from repro.configs import SHAPES_BY_NAME
    from repro.service.cache import request_key
    from repro.service.server import MappingServer, serve_http

    sh = SHAPES_BY_NAME[SHAPE]
    shape = {"name": sh.name, "seq_len": sh.seq_len,
             "global_batch": sh.global_batch, "mode": sh.mode}
    requests = [(ARCH, "rule_based"), (ARCH, "brute_force"),
                (ARCH, "annealing"), (ARCH, "rule_based"),
                ("llama3.2-1b", "brute_force")]
    with MappingServer() as srv:
        httpd = serve_http(srv, "127.0.0.1", 0)
        port = httpd.server_address[1]
        server_thread = threading.Thread(target=httpd.serve_forever,
                                         name="smoke-http", daemon=True)
        server_thread.start()
        try:
            seen = set()
            for arch, optimiser in requests:
                problem, res, kw = direct[(arch, optimiser)]
                repeat = (arch, optimiser) in seen
                seen.add((arch, optimiser))
                name = f"served.{arch}.{optimiser}" + (".repeat" if repeat
                                                       else "")
                with phase(name, clock):
                    got = _post(port, {"arch": arch, "shape": shape,
                                       "exec_model": EXEC_MODEL,
                                       "optimiser": optimiser,
                                       "engine": "jax",
                                       "optimiser_kwargs": kw})
                plan = _plan(problem, res)
                check(got["engine"] == "jax",
                      f"{name}: served by engine {got['engine']!r}")
                check(got["cached"] == repeat,
                      f"{name}: cached={got['cached']}, want {repeat}")
                for field, want in (
                        ("objective_value", plan.objective_value),
                        ("throughput", plan.throughput),
                        ("latency", plan.latency),
                        ("partitions", len(plan.partitions)),
                        ("points", res.points)):
                    check(got[field] == want, f"{name}: {field} "
                          f"{got[field]!r} != direct {want!r}")
                design = srv.cache.get(request_key(problem, optimiser,
                                                   "jax", kw))
                check(design is not None, f"{name}: no cached design")
                served = design.to_result(problem)
                same_result(served, res, f"{name} vs direct call")
                print(f"[{name}] engine={got['engine']} "
                      f"cached={got['cached']} objective="
                      f"{got['objective_value']!r} (== direct)", flush=True)
        finally:
            httpd.shutdown()
            httpd.server_close()
            server_thread.join(60)
    check(not server_thread.is_alive(), "HTTP server thread still running")


# ----------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------

def sharded_phase(clock: CompileClock, devices: int) -> None:
    """devices=D results are identical to devices=1."""
    from repro.configs import SHAPES_BY_NAME
    from repro.core.optimizers import brute_force
    from repro.core.pipeline import optimise_portfolio

    problem = _problem(ARCH)
    with phase("sharded.brute_force.devices1", clock, ("bf_chunk_shard",)):
        one = brute_force(problem, engine="jax", max_points=MAX_POINTS,
                          devices=1)
    with phase(f"sharded.brute_force.devices{devices}", clock,
               ("bf_chunk_shard",)):
        many = brute_force(problem, engine="jax", max_points=MAX_POINTS,
                           devices=devices)
    same_result(many, one, f"brute_force devices={devices} vs 1")
    print(f"[sharded.brute_force] objective={many.evaluation.objective!r} "
          f"points={many.points} (devices={devices} == devices=1)",
          flush=True)

    shape = SHAPES_BY_NAME[SHAPE]
    with phase("sharded.portfolio.devices1", clock,
               ("fleet_bf_chunk_shard",)):
        plans1 = optimise_portfolio(list(SHARDED_PORTFOLIO), shape,
                                    optimiser="brute_force",
                                    exec_model=EXEC_MODEL, engine="jax",
                                    max_points=MAX_POINTS, devices=1)
    with phase(f"sharded.portfolio.devices{devices}", clock,
               ("fleet_bf_chunk_shard",)):
        plans = optimise_portfolio(list(SHARDED_PORTFOLIO), shape,
                                   optimiser="brute_force",
                                   exec_model=EXEC_MODEL, engine="jax",
                                   max_points=MAX_POINTS, devices=devices)
    for name, p1, p in zip(SHARDED_PORTFOLIO, plans1, plans):
        check(p == p1, f"portfolio devices={devices}: {name} plan differs "
                       f"from devices=1")
        print(f"[sharded.portfolio] {name}: objective="
              f"{p.objective_value!r} (devices={devices} == devices=1)",
              flush=True)


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path across four chips")
    args = ap.parse_args(argv)
    try:
        from repro import runtime_config
    except ImportError as err:
        print(f"chip_smoke: the repository's src/ is not next to this "
              f"file ({err})", file=sys.stderr)
        return 2
    runtime_config.apply_env()
    cache_dir = runtime_config.compilation_cache()
    if cache_dir is None:
        print("chip_smoke: jax is unavailable (not installed, or masked "
              "by REPRO_NO_JAX)", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    kind = devs[0].device_kind
    print(f"[device] platform={devs[0].platform} kind={kind} "
          f"count={len(devs)} compile_cache={cache_dir}", flush=True)

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(clock, args.chips)
    else:
        direct = mapping_phase(clock)
        portfolio_phase(clock, direct)
        served_phase(clock, direct)
    print(f"[total] wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.compile_s:.3f} "
          f"cache_hits={clock.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
