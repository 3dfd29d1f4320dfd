"""Jitted-program dispatches per design (the program's
``accel.dispatches.<kind>`` counters, fleet buckets not counted twice)."""

PREFIX = "accel.dispatches."


def read(run):
    t = run.traced
    if t is None or not t["designs"]:
        return None
    calls = sum(v for k, v in t["counters"].items()
                if k.startswith(PREFIX) and "[" not in k)
    return calls / t["designs"]
