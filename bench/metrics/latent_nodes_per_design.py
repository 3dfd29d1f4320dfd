"""Latent-attention nodes in the graph of each design (the program's
counter ``graph.nodes.mla``, added once a request by
``pipeline.make_problem``)."""

NAME = "graph.nodes.mla"


def read(run):
    t = run.traced
    if t is None or not t["designs"] or NAME not in t["counters"]:
        return None
    return t["counters"][NAME] / t["designs"]
