"""Reduce a profiler trace of the window to device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes (with
``jax.profiler.ProfileData``, nothing else) and returns, over the traced
window (the host annotation ``bench.window``):

- ``busy_s``: per device, the union of the intervals in which an XLA
  operation or executable ran (lines ``XLA Ops`` and ``XLA Modules``),
  averaged over the devices, and ``idle_share`` per device;
- ``modules``: device seconds per executable, by its jit name with jax's
  hash dropped (``jit__rb_descend``), averaged over the devices;
- ``collective_s``: device seconds of collective operations (all-reduce,
  all-gather, reduce-scatter, collective-permute, all-to-all), averaged
  over the devices;
- ``breakdown``: the executables that took most device time and the
  idle time attributed to what the host was doing (the deepest program
  span, from ``repro.obs``, around the middle of each gap), ten of each.

A device is a plane named ``/device:TPU:<id>``. Host annotations and
device events share the trace's clock; program spans are moved onto it
through the ``bench.request`` annotations that wrap each request and the
``pipeline.optimise_mapping`` span each request opens.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
REQUEST = "bench.request"
REQUEST_SPAN = "pipeline.optimise_mapping"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?[.(]")
HASH = re.compile(r"\(\d+\)$")
TOP = 10

Interval = Tuple[float, float]


def find_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """The parts of a trace the reduction reads, times in seconds."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    annotations: List[Tuple[str, float, float]] = []
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {
                "ops": [], "modules": [], "collectives": []})
            for line in plane.lines:
                if line.name in ("XLA Ops", "Async XLA Ops"):
                    _read_ops(line.events, dev, line.name == "XLA Ops")
                elif line.name == "XLA Modules":
                    for e in line.events:
                        t = e.start_ns * 1e-9
                        dev["modules"].append(
                            (HASH.sub("", e.name), t, t + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (WINDOW, REQUEST):
                        t = e.start_ns * 1e-9
                        annotations.append((e.name, t,
                                            t + e.duration_ns * 1e-9))
    return {"devices": devices, "annotations": sorted(
        annotations, key=lambda a: a[1])}


def _read_ops(events, dev: dict, busy_line: bool) -> None:
    kinds: Dict[str, bool] = {}
    for e in events:
        name = e.name
        coll = kinds.get(name)
        if coll is None:
            coll = kinds[name] = bool(COLLECTIVE.search(name.split(" = ")[-1]
                                                        if " = " in name
                                                        else name))
        t = e.start_ns * 1e-9
        iv = (t, t + e.duration_ns * 1e-9)
        if coll:
            dev["collectives"].append(iv)
        if busy_line:
            dev["ops"].append(iv)


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals clipped to ``[lo, hi]``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _offset(annotations, spans) -> Optional[float]:
    """Trace-clock seconds minus program-span seconds, from requests."""
    reqs = [a for a in annotations if a[0] == REQUEST]
    opened = sorted(s["start_s"] for s in spans
                    if s["name"] == REQUEST_SPAN)
    pairs = list(zip(reqs, opened))
    if not pairs:
        return None
    diffs = sorted(a[1] - s for a, s in pairs)
    return diffs[len(diffs) // 2]


def _host_doing(spans, offset: Optional[float], t: float) -> str:
    if offset is None:
        return "unattributed"
    x = t - offset
    best = None
    for s in spans:
        if s["start_s"] <= x <= s["start_s"] + s["dur_s"]:
            if best is None or s["depth"] > best["depth"]:
                best = s
    return best["name"] if best is not None else "harness"


def reduce(data: dict, device_ids: Sequence[int],
           spans: Sequence[dict] = ()) -> dict:
    devices = {i: data["devices"][i] for i in device_ids
               if i in data["devices"]}
    if not devices:
        raise ValueError(f"trace has no plane for devices {list(device_ids)}")
    windows = [a for a in data["annotations"] if a[0] == WINDOW]
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:
        ends = [t for d in devices.values() for t in
                [x for iv in d["modules"] for x in iv[1:]]]
        lo, hi = min(ends), max(ends)
    window_s = hi - lo
    busy, idle, modules, gaps = {}, {}, {}, {}
    offset = _offset(data["annotations"], spans)
    for i, dev in devices.items():
        merged = union(dev["ops"] + [iv[1:] for iv in dev["modules"]],
                       lo, hi)
        busy[i] = sum(b - a for a, b in merged)
        idle[i] = 1.0 - busy[i] / window_s if window_s > 0 else None
        for name, a, b in dev["modules"]:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                modules[name] = modules.get(name, 0.0) + (b - a) / len(devices)
        if i == min(devices):
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    what = _host_doing(spans, offset, 0.5 * (a + b))
                    gaps[what] = gaps.get(what, 0.0) + (b - a)
    n = len(devices)
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:TOP]]
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / n,
        "idle_share": idle,
        "modules": modules,
        "collective_s": sum(sum(b - a for a, b in union(
            d["collectives"], lo, hi)) for d in devices.values()) / n,
        "breakdown": {"device_ops": top(modules), "idle_gaps": top(gaps)},
    }


def reduce_dir(trace_dir: str, device_ids: Sequence[int],
               spans: Sequence[dict] = ()) -> dict:
    return reduce(load(find_trace(trace_dir)), device_ids, spans)
