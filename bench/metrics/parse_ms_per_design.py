"""Host milliseconds per design in the parser (the program's span
``graph.build``: ``build_hdgraph`` inside ``pipeline.make_problem``)."""

from metrics._spans import ms_per_design


def read(run):
    return ms_per_design(run, ("graph.build",))
