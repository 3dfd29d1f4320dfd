"""Host milliseconds per design in named program spans."""


def ms_per_design(run, names, outermost=False):
    """Sum of the traced round's spans named in ``names``, per design;
    with ``outermost``, a span inside another of ``names`` is not counted
    again. None where the round holds no such span."""
    t = run.traced
    if t is None or not t["designs"]:
        return None
    spans = [s for s in t["spans"] if s["name"] in names]
    if outermost:
        inner = {s["id"] for s in spans}
        spans = [s for s in spans if s["parent"] not in inner]
    if not spans:
        return None
    return 1e3 * sum(s["dur_s"] for s in spans) / t["designs"]
