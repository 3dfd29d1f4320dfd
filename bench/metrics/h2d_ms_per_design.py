"""Host milliseconds per design packing requests and copying them to the
device (the program's spans ``accel.h2d.rb_descend`` and
``accel.h2d.sa_state``)."""

from metrics._spans import ms_per_design


def read(run):
    return ms_per_design(run, ("accel.h2d.rb_descend", "accel.h2d.sa_state"))
