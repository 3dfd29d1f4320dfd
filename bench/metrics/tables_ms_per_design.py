"""Host milliseconds per design building the per-request move tables of
the device searches (the program's span ``accel.build_sa_tables``)."""

from metrics._spans import ms_per_design


def read(run):
    return ms_per_design(run, ("accel.build_sa_tables",))
