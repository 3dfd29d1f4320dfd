"""Float64 scalar evaluations per design that missed the program's memo
(the program's counter ``optim.host_evals``)."""

NAME = "optim.host_evals"


def read(run):
    t = run.traced
    if t is None or not t["designs"] or NAME not in t["counters"]:
        return None
    return t["counters"][NAME] / t["designs"]
