"""Host milliseconds per design lowering problems for the device (the
program's spans ``accel.lower_program`` and ``accel.build_static_spec``;
the second runs inside the first, so only outermost spans count)."""

NAMES = ("accel.lower_program", "accel.build_static_spec")


def read(run):
    t = run.traced
    if t is None or not t["designs"]:
        return None
    names = {s["id"]: s["name"] for s in t["spans"]}
    total = sum(s["dur_s"] for s in t["spans"] if s["name"] in NAMES
                and names.get(s["parent"]) not in NAMES)
    return 1e3 * total / t["designs"]
