"""Wall seconds of the window over the designs completed in it."""


def read(run):
    return run.window_s / run.designs if run.designs else None
