"""Process start to window start: imports, TPU start-up, lowering, the
compile cache and the warm-up round."""


def read(run):
    return run.setup_s
