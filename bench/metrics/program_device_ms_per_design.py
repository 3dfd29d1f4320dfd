"""Device milliseconds per design in the search programs
(``_rb_descend``, ``_sa_sweeps``, ``_bf_chunk``, ``_bf_chunk_shard``, the
evaluate fused inside), from the profiler trace, averaged over chips."""

from metrics._search import device_s


def read(run):
    t = run.traced
    s = device_s(t and t["trace"])
    if s is None or not t["designs"]:
        return None
    return 1e3 * s / t["designs"]
