"""Shared fixtures. NOTE: no hard-coded XLA_FLAGS here — smoke tests and
benches must see the real single CPU device by default (the 512-device
override belongs exclusively to launch/dryrun.py). Multi-device testing
is an explicit opt-in instead: ``REPRO_FAKE_DEVICES=N pytest ...`` routes
through ``runtime_config.apply_env()`` below — BEFORE anything can
initialise a jax backend — which is how the CI shard job runs the
devices-grid differential tests on 8 fake CPU devices. Without ``REPRO_*``
variables set, ``apply_env`` touches nothing."""
import contextlib

import pytest

from repro import runtime_config

runtime_config.apply_env()

from repro.configs import ARCHS, get_arch, reduced
from repro.core.accel import jax_available

# Without jax (the CI no-jax matrix job, or REPRO_NO_JAX=1) the suite
# still collects and passes: modules whose subject IS jax code are
# skipped wholesale, everything else (core model, constraints, host
# engines, engine-registry fallbacks) runs unchanged.
if not jax_available():
    collect_ignore = [
        "test_accel_engine.py",
        "test_data_checkpoint.py",
        "test_exporter.py",
        "test_integration.py",
        "test_kernels.py",
        "test_models.py",
        "test_optim.py",
        "test_runtime.py",
        "test_shard.py",
        "test_steps.py",
        "test_tpu_compile.py",
    ]
from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.backends import BACKENDS
from repro.core.graph_builder import build_hdgraph
from repro.core.objectives import Problem
from repro.core.platform import Platform


TINY_SHAPE = ShapeSpec("train_tiny", 256, 16, "train")
TINY_DECODE = ShapeSpec("decode_tiny", 256, 16, "decode")


@pytest.fixture(autouse=True)
def _reset_obs_state():
    """Isolate the telemetry layer between tests: tracing off, span
    buffer empty, metrics registry empty. ``TRACE_COUNTS`` keys
    re-materialise at zero (the view is get-or-create), so delta-based
    consumers like ``assert_max_traces`` are unaffected."""
    from repro.obs import metrics, trace
    trace.disable()
    trace.reset()
    metrics.reset()
    yield
    trace.disable()
    trace.reset()
    metrics.reset()


@pytest.fixture
def assert_max_traces():
    """Context manager asserting the jitted accel entry points trace at
    most ``n`` times inside the block — the no-recompile contract.

    ``TRACE_COUNTS`` (core/accel/eval_jax.py) ticks once per TRACE of each
    jitted engine entry point, never per call, so this fixture turns
    "one executable serves the whole portfolio / platform mix / objective
    mix" claims into assertions::

        with assert_max_traces(1):
            fleet_brute_force(problems, ...)

        with assert_max_traces(2, keys=("sa_sweeps",)):   # one entry point
            sa.run(...); sa.run(...)

    ``keys=None`` counts every entry point (brute-force chunks, SA sweeps,
    rule-based descents, standalone evaluate — per-problem and fleet).
    ``exact=True`` requires exactly ``n`` traces instead of at most ``n``
    — use it where the block's shapes are unique in the suite, so a
    silently dropped counter (or a stale uniqueness assumption serving
    the call from cache) fails instead of passing vacuously at 0.
    """
    from repro.core.accel.eval_jax import TRACE_COUNTS

    @contextlib.contextmanager
    def _ctx(n: int, keys=None, exact: bool = False):
        watched = tuple(keys) if keys is not None else tuple(TRACE_COUNTS)
        before = {k: TRACE_COUNTS[k] for k in watched}
        yield TRACE_COUNTS
        grew = {k: TRACE_COUNTS[k] - before[k] for k in watched
                if TRACE_COUNTS[k] != before[k]}
        total = sum(grew.values())
        if exact:
            assert total == n, \
                f"expected exactly {n} traces, got {total}: {grew}"
        else:
            assert total <= n, \
                f"expected <= {n} traces, got {total}: {grew}"

    return _ctx


@pytest.fixture(scope="session")
def tiny_arch() -> ArchConfig:
    return reduced(get_arch("tinyllama-1.1b"))


@pytest.fixture(scope="session")
def small_platform() -> Platform:
    return Platform(name="test-4x4", mesh_axes=(("data", 4), ("model", 4)),
                    hbm_bytes=16 * 2**30)


@pytest.fixture
def tiny_problem(tiny_arch, small_platform) -> Problem:
    graph = build_hdgraph(tiny_arch, TINY_SHAPE)
    return Problem(graph=graph, platform=small_platform,
                   backend=BACKENDS["spmd"], objective="latency",
                   exec_model="spmd")


def make_tiny_problem(arch_name="tinyllama-1.1b", shape=TINY_SHAPE,
                      backend="spmd", objective="latency",
                      exec_model="spmd", platform=None, **opts):
    from repro.core.perfmodel import ModelOptions
    arch = reduced(get_arch(arch_name))
    platform = platform or Platform(
        name="test-4x4", mesh_axes=(("data", 4), ("model", 4)))
    graph = build_hdgraph(arch, shape)
    return Problem(graph=graph, platform=platform,
                   backend=BACKENDS[backend], objective=objective,
                   exec_model=exec_model, opts=ModelOptions(**opts))
