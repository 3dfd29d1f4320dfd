"""The control fails the check; the program passes it.

The control is the plain reference in the program's place, computed in
bfloat16 (``control.py``). For every cell, one round at the cell's own
size on the CPU: each number the program reads is within its limit, and
the control reads above the limit in at least one number.
"""
import json

import pytest

import control
import run
from cells import cpu_chips, with_cells, workloads


@pytest.mark.parametrize("workload", workloads())
def test_control_fails_where_the_program_passes(workload, monkeypatch,
                                                capsys):
    with_cells(monkeypatch)
    monkeypatch.setattr(run, "chips", cpu_chips)
    assert control.main(["--workload", workload, "--seeds", "12345",
                         "--seconds", "0"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limits = line["limits"]
    num = lambda v: float(v)                     # "inf" reads as infinity
    assert all(num(v) <= limits[k] for k, v in line["program"].items())
    assert any(num(v) > limits[k] for k, v in line["control"].items())
