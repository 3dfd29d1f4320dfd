"""Share of the traced window in which no operation ran on the device;
with several chips, the highest of theirs."""


def read(run):
    t = run.traced
    if t is None or t["trace"] is None:
        return None
    shares = [v for v in t["trace"]["idle_share"].values() if v is not None]
    return 100.0 * max(shares) if shares else None
