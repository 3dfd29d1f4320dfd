"""Metric readers, one file per metric, named as in ``BENCHMARK.json``.

Each module has ``read(run) -> float | None``. End-to-end readers read the
whole window (``run.window_s``, ``run.designs``, ``run.setup_s``);
per-layer readers read ``run.traced``, the first round of a ``--trace 1``
window: its ``designs``, ``points`` (as the program reports them),
``counters`` (deltas of the program's counters), ``spans`` (the
program's spans) and ``trace`` (``trace_reduce.reduce`` of the profiler
trace). A reader that finds nothing to read returns None, and the metric
is left out of the result.
"""
