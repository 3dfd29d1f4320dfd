"""Host milliseconds per design in rule-based's Algorithm-2 work between
two descents: merge bookkeeping, propagate, ``repair`` and the float64
evaluations (the program's span ``optim.rb.host``)."""

from metrics._spans import ms_per_design


def read(run):
    return ms_per_design(run, ("optim.rb.host",))
