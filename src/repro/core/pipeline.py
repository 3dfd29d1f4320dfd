"""End-to-end SAMO pipeline: parse -> optimise -> export.

This is the public API the launcher and examples call:

    plan = optimise_mapping(arch, shape, platform, backend="spmd",
                            optimiser="rule_based", objective="throughput")

    plans = optimise_portfolio(["tinyllama-1.1b", "llama3.2-1b"], shape,
                               [zc706_like, u250_like],     # per-model
                               optimiser="brute_force")     # platforms

Engine selection
----------------
Every optimiser evaluates candidate designs through one of three engines
(``core/accel`` registry); ``optimise_mapping(engine=...)`` threads the
choice through. ``auto`` resolves to ``jax`` when jax is importable, else
``numpy``; requesting ``jax`` explicitly without jax installed raises
``core.accel.EngineUnavailable`` naming the missing extra.
(``docs/architecture.md`` maps the engine layers end to end.)

  engine   brute_force                annealing                rule_based
  -------  -------------------------  -----------------------  -----------------
  scalar   one evaluate per point     paper Algorithm 1        scalar probe loop
           (reference; Table-IV       (chains=1 scalar loop;   (reference)
           baseline)                  chains>1 numpy PT)
  numpy    chunked batches through    chains>1: lockstep       each greedy step's
           the vectorised host        parallel tempering, one  probe set as one
           array program             batched evaluate/sweep    batched evaluate
  jax      on-device mixed-radix      whole multi-chain sweep  whole greedy
           candidate decode + jitted  loop on device           descent on device
           evaluate (identical        (lax.scan + jax.random;  (lax.while_loop;
           optimum & history to       per-chain incumbents;    identical move
           numpy)                     different rng than host) sequence, design &
                                                               history to scalar)

Platform notes: the jax engine jit-compiles per trace shape — mode,
backend rule flags, ModelOptions and padded array shapes — and NOT per
platform: resource limits, bandwidth/roofline scalars and the
fold-realisability tables enter the program as device data
(``core/accel/lowering.py``), so switching platforms, or mixing them in
one ``optimise_portfolio`` call, reuses the cached XLA executable. It
runs on whatever ``jax.default_backend()`` provides (CPU jit included;
TPU/GPU when present). The numpy and jax engines evaluate with one array
program (``core/array_program.py``). Device arrays are float32 unless ``jax_enable_x64`` is on; the
scalar/numpy engines are float64 throughout. All engines agree on
feasibility and the returned design; returned ``Evaluation`` objects are
always re-derived through the float64 scalar reference.
"""
from __future__ import annotations

from collections import Counter
from typing import List, Optional, Sequence

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.backends import BACKENDS
from repro.core.exporter import ShardingPlan, default_plan, export_plan
from repro.core.graph_builder import build_hdgraph
from repro.core.objectives import Problem
from repro.core.optimizers import OPTIMIZERS
from repro.core.perfmodel import ModelOptions
from repro.core.platform import Platform, V5E_POD
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace


def make_problem(arch: ArchConfig, shape: ShapeSpec,
                 platform: Platform = V5E_POD,
                 backend: str = "spmd",
                 objective: str = "throughput",
                 exec_model: str = "streaming",
                 opts: Optional[ModelOptions] = None,
                 **model_opts) -> Problem:
    """``model_opts`` are ModelOptions fields (zero1=True, ...) used when no
    explicit ``opts`` is given."""
    if opts is not None and model_opts:
        raise TypeError(f"pass either opts= or ModelOptions fields "
                        f"{sorted(model_opts)}, not both")
    with _trace.span("graph.build", arch=arch.name, shape=shape.name):
        graph = build_hdgraph(arch, shape)
    for kind, count in Counter(n.kind for n in graph.nodes).items():
        _metrics.counter(f"graph.nodes.{kind}").inc(count)
    return Problem(
        graph=graph,
        platform=platform,
        backend=BACKENDS[backend],
        objective=objective,
        exec_model=exec_model,
        opts=opts or ModelOptions(**model_opts),
    )


def optimise_mapping(arch: ArchConfig, shape: ShapeSpec,
                     platform: Platform = V5E_POD,
                     backend: str = "spmd",
                     optimiser: str = "rule_based",
                     objective: str = "throughput",
                     exec_model: str = "streaming",
                     opts: Optional[ModelOptions] = None,
                     engine: Optional[str] = None,
                     **optimiser_kwargs) -> ShardingPlan:
    """``engine`` selects the evaluation engine (see the module docstring
    matrix); None keeps each optimiser's default. Remaining kwargs go to
    the optimiser entry point."""
    with _trace.span("pipeline.optimise_mapping", arch=arch.name,
                     optimiser=optimiser, backend=backend,
                     objective=objective, engine=engine or "default"):
        with _trace.span("pipeline.make_problem"):
            problem = make_problem(arch, shape, platform, backend,
                                   objective, exec_model, opts)
        if engine is not None:
            optimiser_kwargs["engine"] = engine
        with _trace.span("pipeline.optimise", optimiser=optimiser):
            result = OPTIMIZERS[optimiser](problem, **optimiser_kwargs)
        _metrics.counter("optim.host_evals").inc(problem.host_evals)
        with _trace.span("pipeline.export_plan"):
            return export_plan(problem.graph, result.variables, platform,
                               exec_model, result.evaluation)


def optimise_portfolio(archs: Sequence, shapes,
                       platform=V5E_POD,
                       backend: str = "spmd",
                       optimiser: str = "brute_force",
                       objective: str = "throughput",
                       exec_model: str = "streaming",
                       opts: Optional[ModelOptions] = None,
                       engine: str = "auto",
                       devices: Optional[int] = None,
                       **optimiser_kwargs) -> List[ShardingPlan]:
    """Optimise a whole portfolio of (architecture, platform) pairs in one
    fleet sweep.

    ``archs`` is a sequence of ``ArchConfig``s (or registry names);
    ``shapes`` is one ``ShapeSpec`` applied to every arch, or a matching
    sequence. ``platform`` is likewise one ``Platform`` for the whole
    portfolio or a matching sequence of per-problem platforms — platform
    scalars and fold tables are device *data* (``core/accel/lowering.py``),
    so a mixed-platform portfolio shares executables exactly like a
    single-platform one: this is the paper's Table-IV "many networks onto
    many devices" sweep, and f-CNN^x's pick-the-best-platform-per-model
    scenario, as one call. ``objective`` too is one name or a matching
    per-problem sequence: the Eq. 5 objective and the Eq. 4 amortisation
    factor are device data as well, so latency- and throughput-objective
    problems share one bucket and one executable. Mismatched sequence
    lengths raise ``ValueError`` up front. With the ``jax`` engine (the
    ``auto`` default when jax is installed) the problems are bucketed by
    trace signature —
    NOT by platform — padded to a common shape and searched by ONE
    vmapped XLA executable per bucket (``core/accel/fleet.py``); per-
    problem optima, objectives and improvement histories are identical to
    looping ``optimise_mapping(engine="jax")``, at a multiple of its
    aggregate points/s (``benchmarks/run.py fleet [--hetero]``). Without
    jax the portfolio degrades to a per-problem loop on the requested
    host engine.

    Fleet sweeps cover all three optimisers: ``"brute_force"`` (vmapped
    chunk decode), ``"annealing"`` (vmapped multi-chain device SA with
    on-device repair) and ``"rule_based"`` (every problem's Algorithm-2
    greedy descents answered by one vmapped device program per round,
    lanes that converge early idling as no-ops). A portfolio may mix
    platforms AND objectives without splitting executables — both are
    device data. Returns one ``ShardingPlan`` per arch, in input order.

    Duplicate problems — equal ``lowering.problem_fingerprint``, i.e.
    identical canonical lowered programs — are optimised ONCE and the
    single result fans out to every duplicate (the
    ``pipeline.portfolio.coalesced`` counter records how many). The
    fan-out is exact: every engine is deterministic given its seed, so a
    duplicate's re-run would be bit-identical anyway. The only exception
    is ``time_budget_s``, whose wall-clock truncation is not a pure
    function of the problem; budgeted calls keep per-duplicate runs.

    ``devices=D`` additionally shards each fleet bucket's problem lanes
    over the first D visible devices (``shard_map`` over the
    ``runtime_config.device_mesh``; see docs/distributed.md) — results
    stay bit-identical to ``devices=None``. Requires the jax engine.
    """
    from repro.configs import get_arch
    from repro.core.accel import resolve_engine

    # Validate the three input sequences up front with clear errors: a
    # silent zip truncation (or a bare string iterated character by
    # character) used to surface as a baffling failure deep in the
    # lowering instead of here.
    if isinstance(archs, str):
        raise ValueError(
            f"archs must be a sequence of ArchConfigs or registry names; "
            f"got the single string {archs!r} — wrap it in a list")
    archs = [get_arch(a) if isinstance(a, str) else a for a in archs]
    if isinstance(shapes, str) or isinstance(platform, str):
        which = "shapes" if isinstance(shapes, str) else "platform"
        raise ValueError(f"{which} must not be a string — a string would "
                         f"iterate character by character; pass a "
                         f"ShapeSpec/Platform or a sequence of them")
    shapes = [shapes] * len(archs) if isinstance(shapes, ShapeSpec) \
        else list(shapes)
    if len(shapes) != len(archs):
        raise ValueError(f"got {len(archs)} archs but {len(shapes)} "
                         f"shapes; pass one ShapeSpec or exactly one "
                         f"shape per arch")
    platforms = [platform] * len(archs) if isinstance(platform, Platform) \
        else list(platform)
    if len(platforms) != len(archs):
        raise ValueError(f"got {len(archs)} archs but {len(platforms)} "
                         f"platforms; pass one Platform or exactly one "
                         f"platform per arch")
    objectives = [objective] * len(archs) if isinstance(objective, str) \
        else list(objective)
    if len(objectives) != len(archs):
        raise ValueError(f"got {len(archs)} archs but {len(objectives)} "
                         f"objectives; pass one objective or exactly one "
                         f"per arch")
    with _trace.span("pipeline.make_problems", count=len(archs)):
        problems = [make_problem(a, s, p, backend, o, exec_model, opts)
                    for a, s, p, o in
                    zip(archs, shapes, platforms, objectives)]
    eng = resolve_engine(engine)
    # Identical Problems — same canonical lowered program, hence identical
    # results from every deterministic engine — used to be re-validated,
    # re-lowered and re-searched once per duplicate. Coalesce them by the
    # canonical content hash (``lowering.problem_fingerprint``, the same
    # keying path the service cache and the recompile lint's spec builder
    # share) and fan the single result out. Wall-clock budgets are the
    # one knob that makes re-runs non-identical, so budgeted calls keep
    # per-duplicate runs.
    alias_of: dict = {}
    unique_idx = list(range(len(problems)))
    if len(problems) > 1 and "time_budget_s" not in optimiser_kwargs:
        # ``problem_fingerprint`` is deliberately jax-free (it hashes the
        # host-side lowering), so this import works under REPRO_NO_JAX —
        # tests/test_pipeline_engines.py pins the no-jax duplicates path.
        # Dedupe is an optimisation, never a correctness requirement:
        # if fingerprinting is unavailable for any reason, warn and fall
        # back to per-problem runs rather than failing the portfolio.
        try:
            from repro.core.accel.lowering import problem_fingerprint
            with _trace.span("pipeline.dedupe", problems=len(problems)):
                first_at: dict = {}
                unique_idx = []
                for i, p in enumerate(problems):
                    fp = problem_fingerprint(p)
                    if fp in first_at:
                        alias_of[i] = first_at[fp]
                    else:
                        first_at[fp] = i
                        unique_idx.append(i)
        except Exception as e:
            import warnings
            warnings.warn(f"portfolio dedupe unavailable "
                          f"(problem_fingerprint failed: {e}); running "
                          f"every problem individually", RuntimeWarning)
            alias_of = {}
            unique_idx = list(range(len(problems)))
        if alias_of:
            _metrics.counter("pipeline.portfolio.coalesced").inc(
                len(alias_of))
    run_problems = [problems[i] for i in unique_idx]
    if devices is not None:
        if eng != "jax":
            raise ValueError(
                f"devices={devices} requires the jax engine (sharded "
                f"fleets, docs/distributed.md); engine resolved to "
                f"{eng!r}")
        optimiser_kwargs["devices"] = devices
    fleet_kw = {
        "brute_force": {"include_cuts", "max_cuts", "max_points",
                        "batch_size", "devices"},
        "annealing": {"seed", "k_start", "k_min", "cooling", "max_iters",
                      "objective_scale", "chains", "devices"},
        "rule_based": {"multi_start", "devices"},
    }
    # the fleet covers the kwargs above; anything else routes through the
    # per-problem loop, whose results the fleet is bit-identical to
    # anyway. time_budget_s in particular must NOT enter a fleet: budget
    # clocks inside a lockstep bucket would measure the whole portfolio's
    # wall time and truncate each problem differently than its own loop.
    if eng == "jax" and optimiser in fleet_kw \
            and set(optimiser_kwargs) <= fleet_kw[optimiser]:
        from repro.core.accel.fleet import (
            fleet_annealing,
            fleet_brute_force,
            fleet_rule_based,
        )
        runner = {"brute_force": fleet_brute_force,
                  "annealing": fleet_annealing,
                  "rule_based": fleet_rule_based}[optimiser]
        with _trace.span("pipeline.optimise_portfolio.fleet",
                         optimiser=optimiser,
                         problems=len(run_problems)):
            results = runner(run_problems, **optimiser_kwargs)
        # the fleet runners bypass the optimiser entry points (which note
        # their own results), so account for their results here
        for r in results:
            _metrics.note_result(r, engine="fleet")
    else:
        if "devices" in optimiser_kwargs and optimiser != "brute_force":
            extra = sorted(set(optimiser_kwargs)
                           - fleet_kw.get(optimiser, set()))
            raise ValueError(
                f"devices= for optimiser {optimiser!r} is only available "
                f"on the fleet path; kwargs {extra} forced the "
                f"per-problem loop, which has no sharded engine")
        with _trace.span("pipeline.optimise_portfolio.loop",
                         optimiser=optimiser, engine=eng,
                         problems=len(run_problems)):
            results = [OPTIMIZERS[optimiser](p, engine=eng,
                                             **optimiser_kwargs)
                       for p in run_problems]
    # fan the unique results back out over the duplicates, input order
    pos = {orig: k for k, orig in enumerate(unique_idx)}
    all_results = [results[pos[alias_of.get(i, i)]]
                   for i in range(len(problems))]
    with _trace.span("pipeline.export_plans", count=len(all_results)):
        return [export_plan(p.graph, r.variables, p.platform, exec_model,
                            r.evaluation)
                for p, r in zip(problems, all_results)]


def make_comap_problem(archs: Sequence, shape: ShapeSpec,
                       platform: Platform = V5E_POD,
                       backend: str = "spmd",
                       objective: str = "weighted_throughput",
                       weights: Optional[Sequence[float]] = None,
                       exec_model: str = "streaming",
                       opts: Optional[ModelOptions] = None,
                       splits: Optional[Sequence[Sequence[int]]] = None):
    """Build a ``CoMapProblem``: N architectures sharing ONE platform,
    the chip/HBM partition between them part of the decision space
    (docs/comapping.md). ``archs`` are ArchConfigs or registry names;
    ``objective`` is a composite name from ``COMAP_OBJECTIVES``;
    ``splits`` optionally pins an explicit resource-split menu instead
    of the full axis-0 composition enumeration."""
    from repro.configs import get_arch
    from repro.core.objectives import CoMapProblem

    if isinstance(archs, str):
        raise ValueError(
            f"archs must be a sequence of ArchConfigs or registry names; "
            f"got the single string {archs!r} — wrap it in a list")
    archs = [get_arch(a) if isinstance(a, str) else a for a in archs]
    graphs = tuple(build_hdgraph(a, shape) for a in archs)
    return CoMapProblem(
        graphs=graphs,
        platform=platform,
        backend=BACKENDS[backend],
        objective=objective,
        weights=None if weights is None else tuple(weights),
        exec_model=exec_model,
        opts=opts or ModelOptions(),
        splits=None if splits is None
        else tuple(tuple(int(p) for p in s) for s in splits),
    )


def optimise_comapping(archs: Sequence, shape: ShapeSpec,
                       platform: Platform = V5E_POD,
                       backend: str = "spmd",
                       optimiser: str = "rule_based",
                       objective: str = "weighted_throughput",
                       weights: Optional[Sequence[float]] = None,
                       exec_model: str = "streaming",
                       opts: Optional[ModelOptions] = None,
                       engine: str = "auto",
                       splits: Optional[Sequence[Sequence[int]]] = None,
                       **optimiser_kwargs):
    """Jointly map N networks onto one shared platform — the f-CNN^x
    multi-CNN scenario as a first-class problem type.

    Enumerates the resource-partition menu (or the explicit ``splits``),
    searches every per-(split, net) sub-problem with the requested
    optimiser — with the jax engine, ALL S x N lanes as one padded
    fleet program (``core/accel/comap_fleet.py``) — and combines
    per-net optima into the composite ``objective`` on the host in
    float64 (exact: the composites are monotone per-net, see
    ``core/comap.py``). Returns a ``CoMapPlan`` whose ``plans`` hold
    one exported ``ShardingPlan`` per net against its disjoint
    sub-platform; an infeasible co-mapping (e.g. fewer leading-axis
    slices than nets) returns ``feasible=False`` with no plans rather
    than raising. Chosen split, designs, objective and history are
    identical across engines (annealing keeps the stack-wide host/device
    rng caveat)."""
    from repro.core.comap import CoMapPlan, joint_search

    with _trace.span("pipeline.optimise_comapping", nets=len(archs),
                     optimiser=optimiser, objective=objective,
                     engine=engine):
        cp = make_comap_problem(archs, shape, platform, backend,
                                objective, weights, exec_model, opts,
                                splits)
        result = joint_search(cp, optimiser=optimiser, engine=engine,
                              **optimiser_kwargs)
        if result.split_index < 0:
            return CoMapPlan(split_index=-1, split=(), plans=(),
                             objective=objective,
                             objective_value=result.evaluation.objective,
                             feasible=False, result=result)
        subplats = cp.split_platforms(result.split_index)
        with _trace.span("pipeline.export_plans", count=cp.n_nets):
            plans = tuple(
                export_plan(cp.graphs[i], r.variables, subplats[i],
                            exec_model, r.evaluation)
                for i, r in enumerate(result.per_net))
        return CoMapPlan(split_index=result.split_index,
                         split=result.split, plans=plans,
                         objective=objective,
                         objective_value=result.evaluation.objective,
                         feasible=result.evaluation.feasible,
                         result=result)


def baseline_plan(arch: ArchConfig, shape: ShapeSpec,
                  platform: Platform = V5E_POD,
                  exec_model: str = "spmd") -> ShardingPlan:
    """Unoptimised (paper Table V *init.*) single-partition pure-DP plan."""
    graph = build_hdgraph(arch, shape)
    return default_plan(graph, platform, exec_model=exec_model)
