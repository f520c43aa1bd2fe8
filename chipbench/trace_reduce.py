"""From a profiler trace (``.xplane.pb``) to numbers: device busy/idle by the
union of op intervals, device time per XLA module and per op, and the longest
idle gaps named by what the host was doing. Reads the file with
``jax.profiler.ProfileData`` and nothing else.

A device plane is one whose name starts with ``/device:TPU:``; on it the line
``XLA Ops`` holds one event per executed op and ``XLA Modules`` one per
executed program. Host planes (``/host:CPU``) hold one line per thread.
"""

from __future__ import annotations

import json
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def read_planes(path: str, min_host_ns: float = 1e6) -> list[dict]:
    """The trace as plain data: planes -> lines -> (name, start_ns, dur_ns).
    Host events shorter than ``min_host_ns`` are dropped while reading: they
    only serve to name idle gaps, and the Python tracer writes millions."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        floor = 0.0 if plane.name.startswith("/device:") else min_host_ns
        lines = []
        for line in plane.lines:
            events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events if ev.duration_ns >= floor]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union_seconds(intervals) -> float:
    """Total length of the union of (start_ns, dur_ns) intervals, in seconds."""
    total, end = 0.0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total / 1e9


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce(planes: list[dict], top: int = 10) -> dict:
    """busy_s (mean over device planes), span of the device activity, device
    seconds per module and per op (summed over devices), the longest gaps."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    busy, ops, modules, gaps = [], {}, {}, []
    first, last = None, None
    host = [(n, s, d) for p in planes if not p["name"].startswith("/device:")
            for line in p["lines"] for n, s, d in line["events"]]
    for plane in devices:
        events = _line(plane, OPS_LINE)
        if not events:
            continue
        busy.append(union_seconds((s, d) for _, s, d in events))
        for name, _, dur in events:
            ops[name] = ops.get(name, 0.0) + dur / 1e9
        for name, _, dur in _line(plane, MODULES_LINE):
            modules[name] = modules.get(name, 0.0) + dur / 1e9
        ordered = sorted((s, s + d) for _, s, d in events)
        first = ordered[0][0] if first is None else min(first, ordered[0][0])
        end = ordered[0][1]
        for start, stop in ordered[1:]:
            if start > end:
                gaps.append((start - end, end, start))
            end = max(end, stop)
        last = end if last is None else max(last, end)
    gaps.sort(reverse=True)
    named = []
    for length, g0, g1 in gaps[:top]:
        named.append([_host_activity(host, g0, g1), length / 1e9])
    rank = lambda d: [[k[:160], v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "devices": len(busy),
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "active_span_s": (last - first) / 1e9 if busy else 0.0,
        "device_ops": rank(ops),
        "modules": modules,
        "idle_gaps": named,
    }


def _host_activity(host, g0: float, g1: float) -> str:
    """What the host was doing in the gap [g0, g1] (ns): the shortest host
    event that covers nine tenths of it (the innermost frame of the Python
    tracer), else the one that covers most."""
    inner, inner_dur = None, None
    best, best_cover = "host: nothing traced", 0.0
    for name, start, dur in host:
        cover = min(g1, start + dur) - max(g0, start)
        if cover >= 0.9 * (g1 - g0) and (inner_dur is None or dur < inner_dur):
            inner, inner_dur = name, dur
        if cover > best_cover:
            best, best_cover = name, cover
    return inner or best
