"""``build_sa_tables``' clamp table against the divisor walk-down.

``_walk_down_clamp`` is the table as it was first written: for every
variable, node and value ``v``, step down from ``v`` until the node's
dimension divides it. ``build_sa_tables`` computes the same table once per
distinct dimension with array arithmetic; it must agree bit for bit on the
registry's cell graphs and on hand-made dimensions, unpadded and with every
pad, and keep the padding rules (padded node rows all ones, column 0 of a
real node 0, int64).
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import SHAPES_BY_NAME
from repro.core.backends import VARS
from repro.core.pipeline import make_problem
from repro.core.platform import V5E_POD
from repro.obs import metrics

pytest.importorskip("jax")
from repro.core.accel.search_loops import build_sa_tables  # noqa: E402

from conftest import TINY_SHAPE  # noqa: E402

# the node attribute each fold variable must divide
_DIM_ATTR = {"s_in": "rows", "s_out": "col_div", "kern": "batch"}


def _walk_down_clamp(graph, max_val, n_pad):
    clamp = np.ones((3, n_pad, max_val + 1), np.int64)
    for vi, var in enumerate(VARS):
        for j, node in enumerate(graph.nodes):
            dim = getattr(node, _DIM_ATTR[var])
            for v in range(max_val + 1):
                val = v
                while val > 1 and dim % val != 0:
                    val -= 1
                clamp[vi, j, v] = val
    return clamp


def _is_prime(k):
    return k > 1 and all(k % d for d in range(2, int(k ** 0.5) + 1))


def _hand_made_problem():
    """A small graph whose dimensions are 1, a prime above the largest fold
    value, a prime below it, a power of two and the largest fold value."""
    vmax = max(V5E_POD.fold_values())
    above = next(k for k in range(vmax + 1, 4 * vmax) if _is_prime(k))
    below = next(k for k in range(vmax - 1, 1, -1) if _is_prime(k))
    pow2 = 1 << (vmax.bit_length() - 2)
    dims = (1, above, below, pow2, vmax)
    prob = make_problem(get_arch("tinyllama-1.1b"), TINY_SHAPE,
                        exec_model="spmd")
    m = len(dims)
    prob.graph.nodes = [
        dataclasses.replace(node, rows=dims[j % m],
                            col_divisor=dims[(j + 1) % m],
                            batch=dims[(j + 2) % m])
        for j, node in enumerate(prob.graph.nodes)]
    return prob


_CELL_GRAPHS = {
    "stablelm-3b": "train_4k",
    "jamba-1.5-large-398b": "train_4k",
    "kimi-k2-1t-a32b": "decode_32k",
}


def _problem(name):
    if name == "hand-made":
        return _hand_made_problem()
    return make_problem(get_arch(name), SHAPES_BY_NAME[_CELL_GRAPHS[name]],
                        exec_model="spmd")


def _distinct_dims(graph):
    return {getattr(node, _DIM_ATTR[var])
            for var in VARS for node in graph.nodes}


@pytest.mark.parametrize("padded", [False, True], ids=["unpadded", "padded"])
@pytest.mark.parametrize("name", [*_CELL_GRAPHS, "hand-made"])
def test_clamp_matches_walk_down(name, padded):
    prob = _problem(name)
    n = len(prob.graph.nodes)
    vmax = max(prob.platform.fold_values())
    if padded:
        mm = build_sa_tables(prob)[0].shape[-1]
        n_pad, max_val = n + 3, vmax + 44
        tables = build_sa_tables(prob, pad_nodes=n_pad, pad_menu=mm + 2,
                                 pad_val=max_val)
    else:
        n_pad, max_val = n, vmax
        tables = build_sa_tables(prob)
    clamp = tables[2]
    assert clamp.dtype == np.int64
    assert clamp.shape == (3, n_pad, max_val + 1)
    np.testing.assert_array_equal(
        clamp, _walk_down_clamp(prob.graph, max_val, n_pad))
    assert (clamp[:, n:] == 1).all()
    assert (clamp[:, :n, 0] == 0).all()


@pytest.mark.parametrize("name", [*_CELL_GRAPHS, "hand-made"])
def test_clamp_dims_counter(name):
    prob = _problem(name)
    counter = metrics.counter("accel.tables.clamp_dims")
    before = counter.value
    build_sa_tables(prob, pad_nodes=len(prob.graph.nodes) + 3)
    assert counter.value - before == len(_distinct_dims(prob.graph))
