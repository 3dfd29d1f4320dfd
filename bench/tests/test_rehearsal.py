"""CPU rehearsal: one round of every cell's request loop, through
``run.main``, with the look for a TPU stubbed out. Each answer must pass
the check against the reference, nothing may compile inside the window,
and the result line must carry the cell's metrics."""
import json
import os

import pytest

import run
import trace_reduce
from cells import result, with_cells, workloads

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("workload", workloads())
def test_one_round_is_correct(workload, monkeypatch, capsys):
    with_cells(monkeypatch)
    res = result(monkeypatch, capsys, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"design_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


def test_no_compile_inside_the_window(monkeypatch, capsys):
    seen = {}
    real = run.measure

    def spy(*a, **kw):
        out = real(*a, **kw)
        seen["run"] = out["run"]
        return out

    monkeypatch.setattr(run, "measure", spy)
    with_cells(monkeypatch)
    result(monkeypatch, capsys, "stablelm-3b.bf")
    assert seen["run"].compiles_in_window == 0


def test_traced_run_reports_per_layer_metrics(monkeypatch, capsys):
    """``--trace 1`` on the CPU has no TPU planes to read; the reduction of
    a trace recorded on the chip stands in for it."""
    recorded = trace_reduce.load(os.path.join(DATA, "bf_8chunks.xplane.pb"))
    monkeypatch.setattr(trace_reduce, "reduce_dir",
                        lambda d, ids, spans=(): trace_reduce.reduce(
                            recorded, [0], spans))
    res = result(monkeypatch, capsys, "stablelm-3b.rb", trace=1)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"] for m in json.load(f)["per_layer"]
                     if "stablelm-3b.rb" in m["workloads"]}
    assert res["correct"]
    assert set(res["metrics"]) == per_layer
    assert res["device"]["busy_s"] > 0
    assert len(res["breakdown"]["device_ops"]) <= 10


def test_no_accelerator_no_result(capsys):
    """On the CPU, without the stub, the run refuses and prints nothing."""
    code = run.main(["--workload", "stablelm-3b.rb", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert code == 3
    assert capsys.readouterr().out == ""


def test_bare_checkout_refuses(tmp_path, monkeypatch, capsys):
    """A directory with only BENCHMARK.json and bench/ has no program."""
    import program
    monkeypatch.setattr(program, "SRC", str(tmp_path / "src"))
    code = run.main(["--workload", "stablelm-3b.rb", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert code == 2
    assert capsys.readouterr().out == ""
