"""Compile the jax engine's device programs for a TPU v5e without a chip.

The TPU compiler is installed with jax: it compiles for a topology that is
described rather than attached, and refuses what the chip would refuse
(layouts, memory) at no chip time. Each test compiles one
jitted entry point exactly as the engine dispatches it — the arguments are
captured at the engine's own call site and replaced by shapes placed on a
described v5e chip — at real node counts: tinyllama-1.1b train_4k (47
nodes) and qwen2-vl-72b train_4k (163 nodes, the largest registered
graph). Nothing runs, so these say nothing about results or times.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and every xdist worker imports this
file. Keep every such compile in this one file, so that one worker loads
the library.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec,
    SingleDeviceSharding,
)

from repro.configs import SHAPES_BY_NAME, get_arch  # noqa: E402
from repro.core.accel import eval_jax, search_loops  # noqa: E402
from repro.core.accel.eval_jax import JaxEvaluator  # noqa: E402
from repro.core.optimizers import OPTIMIZERS  # noqa: E402
from repro.core.pipeline import make_problem  # noqa: E402

ARCHS = ("tinyllama-1.1b", "qwen2-vl-72b")
BATCH = 4096            # the brute-force chunk size (batch_size default)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")      # else libtpu logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def problems():
    shape = SHAPES_BY_NAME["train_4k"]
    return {a: make_problem(get_arch(a), shape, exec_model="spmd")
            for a in ARCHS}


class _Captured(Exception):
    pass


def _capture(monkeypatch, name, run):
    """The positional arguments the engine passes to ``search_loops.<name>``
    (the call is stopped there, before anything is dispatched)."""
    def record(*args):
        raise _Captured(args)
    monkeypatch.setattr(search_loops, name, record)
    with pytest.raises(_Captured) as info:
        run()
    monkeypatch.undo()
    return info.value.args[0]


def _on(sharding, static_n, args):
    """Arguments past the first ``static_n`` as shapes on ``sharding``."""
    def shape(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return x
    return (*args[:static_n],
            *(jax.tree_util.tree_map(shape, a) for a in args[static_n:]))


def _compile(fn, args):
    compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("arch", ARCHS)
def test_evaluate_batch_compiles_for_v5e(arch, problems, one_chip,
                                         no_persistent_cache):
    jev = JaxEvaluator.from_problem(problems[arch])
    n = jev.static.n_nodes
    folds = np.ones((BATCH, n), np.int32)
    cuts = np.zeros((BATCH, n - 1), bool)
    _compile(eval_jax.evaluate_batch_jax,
             _on(one_chip, 1, (jev.static, jev.arrays, folds, folds, folds,
                               cuts)))


@pytest.mark.parametrize("arch", ARCHS)
def test_bf_chunk_compiles_for_v5e(arch, problems, one_chip,
                                   no_persistent_cache, monkeypatch):
    args = _capture(monkeypatch, "_bf_chunk", lambda: OPTIMIZERS[
        "brute_force"](problems[arch], engine="jax", max_points=1))
    assert args[1] == BATCH
    _compile(search_loops._bf_chunk, _on(one_chip, 3, args))


@pytest.mark.parametrize("arch", ARCHS)
def test_sa_sweeps_compile_for_v5e(arch, problems, one_chip,
                                   no_persistent_cache, monkeypatch):
    args = _capture(monkeypatch, "_sa_sweeps", lambda: OPTIMIZERS[
        "annealing"](problems[arch], engine="jax", chains=32))
    _compile(search_loops._sa_sweeps, _on(one_chip, 4, args))


@pytest.mark.parametrize("arch", ARCHS)
def test_rb_descend_compiles_for_v5e(arch, problems, one_chip,
                                     no_persistent_cache, monkeypatch):
    args = _capture(monkeypatch, "_rb_descend", lambda: OPTIMIZERS[
        "rule_based"](problems[arch], engine="jax"))
    _compile(search_loops._rb_descend, _on(one_chip, 2, args))


def test_bf_chunk_shard_compiles_for_v5e_2x2(topo, problems,
                                             no_persistent_cache,
                                             monkeypatch):
    """The devices=4 chunk program: BATCH rows on each chip of the 2x2
    mesh, the incumbent combined by all-reduce collectives."""
    args = _capture(monkeypatch, "_bf_chunk", lambda: OPTIMIZERS[
        "brute_force"](problems["tinyllama-1.1b"], engine="jax",
                       max_points=1))
    mesh = Mesh(np.asarray(topo.devices), ("dev",))
    replicated = NamedSharding(mesh, PartitionSpec())
    static, B, no_cut, *rest = _on(replicated, 3, args)
    compiled = _compile(search_loops._bf_chunk_shard,
                        (static, B * mesh.devices.size, no_cut, mesh, *rest))
    assert "all-reduce" in compiled.as_text()
