"""The reader of ``optim.repair.candidates``: the counter over the traced
round's designs, and nothing where the program has no such counter."""
from types import SimpleNamespace

from metrics import repair_candidates_per_design as reader


def _run(counters, designs=4):
    return SimpleNamespace(traced={"designs": designs, "points": 10,
                                   "counters": counters, "spans": [],
                                   "trace": None})


def test_reads_the_counter_over_designs():
    run = _run({"optim.repair.candidates": 17340})
    assert reader.read(run) == 17340 / 4


def test_reads_none_without_the_counter():
    assert reader.read(_run({"optim.host_evals": 12})) is None
    assert reader.read(_run({"optim.repair.candidates": 5}, designs=0)) is None
    assert reader.read(SimpleNamespace(traced=None)) is None
