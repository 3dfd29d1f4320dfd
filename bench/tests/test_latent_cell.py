"""The latent-attention cell (``kimi-k2-1t-a32b.rb``): its control fails
in prefill and in decode alike, and a fault planted in the program's
latent-state sharding term reads ``correct: false``.

The check of the cell (``checks/search_latent.py``) runs the latent
reference (``reference/latent_moe.py``) once a variant; these tests run
it at the cell's own size on the CPU.
"""
import numpy as np
import pytest

import run
from cells import cpu_chips, result
from repro.core import batched_eval

CELL = "kimi-k2-1t-a32b.rb"


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


def test_latent_state_term_left_out(monkeypatch, capsys):
    """The engines lose the latent rule: the cache divides over the head
    fold like any other node's state, and its ring exchange and decode
    combine read per-head widths. The float64 host re-evaluation keeps
    the rule, so the designs the device search reaches are checked
    against it."""
    real = batched_eval.BatchedEvaluator._lower

    def lower_without_latent(self):
        real(self)
        self.latent_dim = np.zeros_like(self.latent_dim)
        self.i_latent = np.zeros(0, np.int64)

    monkeypatch.setattr(batched_eval.BatchedEvaluator, "_lower",
                        lower_without_latent)
    res = result(monkeypatch, capsys, CELL)
    assert not res["correct"], res["checks"]


def test_program_without_latent_attention(monkeypatch, capsys):
    """A program that maps the model's attention as per-head attention
    (``attn`` where the chain has ``mla``), as one without the latent kind
    does, cannot run the cell: no plan covers the reference's chain, and
    the run stops at the check with a non-zero exit and no result line."""
    from repro.configs.base import ArchConfig
    real = ArchConfig.layer_kind

    def no_latent(self, i):
        kind = real(self, i)
        return "attn" if kind == "mla" else kind

    monkeypatch.setattr(ArchConfig, "layer_kind", no_latent)
    monkeypatch.setattr(run, "chips", cpu_chips)
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", CELL, "--seed", "4294967311",
                  "--seconds", "0", "--trace", "0"])
    assert "does not cover the configuration's 185-node chain" in str(
        stop.value)
    assert "correct" not in capsys.readouterr().out
