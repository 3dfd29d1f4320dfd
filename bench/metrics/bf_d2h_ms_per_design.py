"""Host milliseconds per design blocked in brute force's per-chunk
readback (the program's span ``accel.d2h.bf_chunk``)."""

NAME = "accel.d2h.bf_chunk"


def read(run):
    t = run.traced
    if t is None or not t["designs"]:
        return None
    spans = [s["dur_s"] for s in t["spans"] if s["name"] == NAME]
    if not spans:
        return None
    return 1e3 * sum(spans) / t["designs"]
