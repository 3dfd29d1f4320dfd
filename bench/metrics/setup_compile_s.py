"""Seconds jax spent tracing, lowering and compiling (or loading from the
persistent cache) during set-up, from its monitoring events."""


def read(run):
    return run.setup_compile_s
