"""The trace reduction, on a trace recorded on one TPU v5e chip: one
brute-force request (stablelm-3b, prefill_32k, latency) of 8 chunks of
4096 rows, with the benchmark's profiler options, and the program spans
of that request (``data/``)."""
import json
import os

import pytest

import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    data = T.load(os.path.join(DATA, "bf_8chunks.xplane.pb"))
    with open(os.path.join(DATA, "bf_8chunks.spans.json")) as f:
        spans = json.load(f)
    return data, spans


def test_union_merges_and_clips():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (5.0, 6.0)]
    assert T.union(ivs, 0.25, 5.5) == [(0.25, 2.0), (3.0, 4.0), (5.0, 5.5)]
    assert T.union([], 0.0, 1.0) == []


def test_recorded_trace_has_the_chip_and_the_window(recorded):
    data, _ = recorded
    assert 0 in data["devices"]
    names = [a[0] for a in data["annotations"]]
    assert names.count(T.WINDOW) == 1 and names.count(T.REQUEST) == 1
    chunks = [m for m in data["devices"][0]["modules"]
              if m[0] == "jit__bf_chunk"]
    assert len(chunks) == 8


def test_reduction(recorded):
    data, spans = recorded
    r = T.reduce(data, [0], spans)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 <= r["idle_share"][0] < 1
    chunk_s = r["modules"]["jit__bf_chunk"]
    # busy time is the union of the op and executable intervals: at least
    # the chunk programs' own time, at most the window
    assert chunk_s <= r["busy_s"] + 1e-9
    assert r["collective_s"] == 0.0          # one chip: no collectives
    ops = r["breakdown"]["device_ops"]
    assert ops[0][0] == "jit__bf_chunk" and len(ops) <= T.TOP
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9, abs=1e-12)
    assert "unattributed" not in gaps        # spans line up with the trace


def test_missing_device_is_an_error(recorded):
    data, _ = recorded
    with pytest.raises(ValueError):
        T.reduce(data, [3])
