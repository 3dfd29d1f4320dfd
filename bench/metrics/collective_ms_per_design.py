"""Device milliseconds per design in collective operations across chips
(the sharded chunk's ``pmin``/``psum``), from the trace, averaged over
chips."""


def read(run):
    t = run.traced
    if t is None or t["trace"] is None or not t["designs"]:
        return None
    s = t["trace"]["collective_s"]
    return 1e3 * s / t["designs"] if s > 0 else None
