"""Fold moves per design that the feasibility repair scored (the
program's counter ``optim.repair.candidates``: every candidate that
reaches ``set_fold``)."""

NAME = "optim.repair.candidates"


def read(run):
    t = run.traced
    if t is None or not t["designs"] or NAME not in t["counters"]:
        return None
    return t["counters"][NAME] / t["designs"]
