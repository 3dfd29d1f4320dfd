"""Host milliseconds per design blocked reading results back from the
device (the program's spans ``accel.d2h.rb_descend``,
``accel.d2h.sa_sweeps`` and ``accel.d2h.sa_best``). A readback waits for
the program before it, so these spans hold the device time the host
sees."""

from metrics._spans import ms_per_design


def read(run):
    return ms_per_design(run, ("accel.d2h.rb_descend", "accel.d2h.sa_sweeps",
                               "accel.d2h.sa_best"))
