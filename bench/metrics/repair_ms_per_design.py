"""Host milliseconds per design in the greedy feasibility repair (the
program's span ``optim.repair``; only outermost spans count)."""

from metrics._spans import ms_per_design


def read(run):
    return ms_per_design(run, ("optim.repair",), outermost=True)
