"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-op device time, and idle gaps attributed to what the host was doing.

The device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op, named by its HLO text, whose instruction name
is kept (a Pallas kernel appears under its jitted function's name, e.g.
``paged_attention_kernel.12``; asynchronous copies sit on another line and
do not count).  Host and device clocks agree to about a millisecond.  Host spans are the
``TraceAnnotation`` events on the host plane.  Only the part of the trace
inside the host span ``window`` counts: busy time is the union of the op
intervals there, averaged over the devices that ran any op; an idle gap is
charged to the innermost host span of ``host_spans`` that covers its
midpoint, or to ``"other"``.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# control-flow ops span the ops of their bodies: busy time, not op time
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def host_events(pd):
    """(name, start_ns, end_ns) of every host span, all threads."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def device_ops(pd):
    """{device plane name: [(op name, start_ns, end_ns)]}."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [(op_name(ev.name), ev.start_ns,
                                    ev.start_ns + ev.duration_ns)
                                   for ev in line.events]
    return out


def reduce(path: str, window: str, host_spans=()) -> dict:
    """Busy/idle seconds, per-op seconds and attributed idle gaps inside the
    host span named ``window``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    hosts = host_events(pd)
    wins = [(a, b) for n, a, b in hosts if n == window]
    if not wins:
        raise ValueError(f"no host span {window!r} in {path}")
    w0, w1 = wins[0]
    spans = [(n, a, b) for n, a, b in hosts if n in host_spans]
    busy, op_s, idle = [], {}, {}
    for ops in device_ops(pd).values():
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
                   if b > w0 and a < w1]
        if not clipped:
            continue
        for n, a, b in clipped:
            if not CONTAINERS.match(n):
                op_s[n] = op_s.get(n, 0.0) + (b - a) * 1e-9
        u = _union([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
            who = min(cover)[1] if cover else "other"
            idle[who] = idle.get(who, 0.0) + (b - a) * 1e-9
    n_dev = max(1, len(busy))
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n_dev,
        "devices": len(busy),
        "op_s": {k: v / n_dev for k, v in op_s.items()},
        "idle_s": {k: v / n_dev for k, v in idle.items()},
    }


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def op_seconds(red: dict, pattern: str) -> float:
    """Device seconds of every op whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in red["op_s"].items() if rx.match(k))
