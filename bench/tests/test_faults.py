"""A run with the timed path broken underneath must come out not correct.

Each test drives one round of a cell through ``run.main`` on the CPU (the
look for a TPU stubbed out) with one fault planted in the program:

- an answer altered where it is produced: the optimiser's design has one
  fold changed before the plan is exported (every cell);
- half of the batch left out: the first half of every chunk's candidate
  rows reads infeasible, while the points reported stay the same (the
  brute force cells). Left out, the second half changes no answer of
  these mixes: their optima lie in the first halves of their chunks;
- the exchange between chips left out: the sharded chunk's ``pmin`` and
  ``psum`` return each chip's own value (the four-chip mix, on four
  host devices).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from cells import D4, result, with_cells, workloads
from repro.core import optimizers
from repro.core.accel import search_loops
from repro.core.optimizers.common import OptimResult

BRUTE_FORCE = ["stablelm-3b.bf", D4["name"]]


def _altered(result_: OptimResult) -> OptimResult:
    """Node 0's kern raised to another value of its menu, re-evaluated,
    so the plan is consistent with the design it states."""
    v = result_.variables
    kern = list(v.kern)
    kern[0] = 16 if kern[0] != 16 else 1
    v2 = dataclasses.replace(v, kern=tuple(kern))
    return dataclasses.replace(result_, variables=v2)


@pytest.mark.parametrize("workload", workloads())
def test_answer_altered(workload, monkeypatch, capsys):
    with_cells(monkeypatch)
    for name, fn in list(optimizers.OPTIMIZERS.items()):
        monkeypatch.setitem(optimizers.OPTIMIZERS, name,
                            lambda p, _fn=fn, **kw: _altered(_fn(p, **kw)))
    res = result(monkeypatch, capsys, workload)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", BRUTE_FORCE)
def test_half_the_batch_left_out(workload, monkeypatch, capsys):
    with_cells(monkeypatch)
    def half(eval_part):
        def part(static, B, no_cut, A, si, so, kk, cb_row, take, start=0):
            objs, *best = eval_part(static, B, no_cut, A, si, so, kk,
                                    cb_row, take, start)
            rows = start + jnp.arange(B)
            objs = jnp.where(rows % 4096 < 2048, jnp.inf, objs)
            r = jnp.argmin(objs)
            return (objs, si[r], so[r], kk[r])
        return part

    monkeypatch.setattr(search_loops, "_bf_eval_part",
                        half(search_loops._bf_eval_part))
    jax.clear_caches()
    try:
        res = result(monkeypatch, capsys, workload)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["checks"]


def test_exchange_between_chips_left_out(monkeypatch, capsys):
    """Without the exchange each chip keeps its own best row, and the
    replicated output takes the first chip's (the replication check is
    switched off, as a program that dropped the exchange would have to)."""
    with_cells(monkeypatch)
    shard_map = jax.shard_map
    monkeypatch.setattr(jax.lax, "pmin", lambda x, axis_name: x)
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name: x)
    monkeypatch.setattr(jax, "shard_map", functools.partial(
        shard_map, check_vma=False))
    jax.clear_caches()
    try:
        res = result(monkeypatch, capsys, D4["name"])
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["checks"]
