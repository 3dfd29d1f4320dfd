"""Device nanoseconds per design point the program reports evaluating,
in the search programs (profiler trace), averaged over chips."""

from metrics._search import device_s


def read(run):
    t = run.traced
    s = device_s(t and t["trace"])
    if s is None or not t["points"]:
        return None
    return 1e9 * s / t["points"]
