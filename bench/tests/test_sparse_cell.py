"""The sparse-attention cell (``deepseek-v3.2-671b-a37b.rb``): its control
fails in prefill and in decode alike, each of three faults planted in the
program's sparse-attention terms reads ``correct: false``, and a program
that maps the model as plain latent attention stops at the check.

The check of the cell (``checks/search_sparse.py``) runs the sparse
reference (``reference/sparse_latent.py``) once a variant; these tests
run it at the cell's own size on the CPU.
"""
import numpy as np
import pytest

import run
from cells import cpu_chips, result
from repro.core import batched_eval

CELL = "deepseek-v3.2-671b-a37b.rb"


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_bfloat16_control_fails_in_each_mode(mode):
    spec = run.load_cell(CELL)
    config, traffic = spec["config"], spec["traffic"]
    check = run.module("checks", traffic["check"])
    used = [i for i, v in enumerate(traffic["variants"]) if v["mode"] == mode]
    answers = [{"variant": i, "plan": None} for i in used]
    claims = check.control_claims(answers, config, traffic, {})
    ctrl = check.check(answers, config, traffic, claims=claims)
    limits = traffic["limits"]
    assert any(v > limits[k] for k, v in ctrl.items()), ctrl
    # the reference's own float64 answers pass
    own = check.reference_answers(config, traffic, set(used))
    assert check.check(answers, config, traffic, claims=own) == {
        "design_mismatch": 0, "objective_rel_err": 0.0}


def _indexer_over_heads(bev):
    """Rule a lost: the indexer's FLOPs and weights divide over the head
    fold like the core's."""
    bev.indexer_flops = np.zeros_like(bev.indexer_flops)
    bev.indexer_weight_bytes = np.zeros_like(bev.indexer_weight_bytes)


def _whole_cache_read(bev):
    """Rule b lost: a decode step reads both whole caches, as dense
    latent attention would."""
    bev.state_io_bytes = np.where(bev.state_io_bytes > 0, bev.state_bytes,
                                  0.0)


def _no_top_k_merge(bev):
    """Rule c lost: a split-KV decode pays no all-gather of candidates."""
    bev.index_topk = np.zeros_like(bev.index_topk)


@pytest.mark.parametrize("fault", [_indexer_over_heads, _whole_cache_read,
                                   _no_top_k_merge],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_in_a_sparse_term_is_not_correct(fault, monkeypatch, capsys):
    """The engines lose one rule. The float64 host re-evaluation keeps
    it, so the designs the device search reaches are checked against
    it."""
    real = batched_eval.BatchedEvaluator._lower

    def lower_with_fault(self):
        real(self)
        fault(self)

    monkeypatch.setattr(batched_eval.BatchedEvaluator, "_lower",
                        lower_with_fault)
    res = result(monkeypatch, capsys, CELL)
    assert not res["correct"], res["checks"]


def test_program_mapping_plain_latent_attention(monkeypatch, capsys):
    """A program that maps the model's attention as dense latent
    attention (``mla`` where the chain has ``dsa``), as one without the
    sparse kind does, cannot run the cell: no plan covers the reference's
    chain, and the run stops at the check with a non-zero exit and no
    result line."""
    from repro.configs.base import ArchConfig
    real = ArchConfig.layer_kind

    def no_sparse(self, i):
        kind = real(self, i)
        return "mla" if kind == "dsa" else kind

    monkeypatch.setattr(ArchConfig, "layer_kind", no_sparse)
    monkeypatch.setattr(run, "chips", cpu_chips)
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", CELL, "--seed", "4294967311",
                  "--seconds", "0", "--trace", "0"])
    assert "does not cover the configuration's 183-node chain" in str(
        stop.value)
    assert "correct" not in capsys.readouterr().out
