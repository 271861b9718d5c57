"""Reduction of a profiler trace to the benchmark's per-layer numbers.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler.trace``
writes into plain lists: for each device plane the intervals of its
operations, and the benchmark's own host spans (``bench.*``
``TraceAnnotation``s). ``reduce_events`` turns those lists into the
device's busy time, idle share, per-operation totals, collective time and
the longest idle gaps with what the host was doing in each. On a TPU the
ops line nests: a ``while`` loop's event covers the events of its body,
so busy time is a union and an operation's total is its own events' sum. Both are
kept apart so that the arithmetic is tested on a small recorded trace
(``tests/data/``) without a chip.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

# The line of a TPU device plane that holds one event per executed
# operation; the other lines (modules, steps, framework scopes) cover the
# same time again at coarser grain.
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
COLLECTIVE_MARKERS = ("all-reduce", "allreduce")

Interval = Tuple[float, float]


def load_events(trace_dir: str) -> dict:
    """``{"devices": {plane: [[op, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]}`` from the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            devices[plane.name] = [
                [op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in lines[OPS_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIX))
    return {"devices": devices, "host": host}


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``: a TPU
    trace names each operation by its whole HLO instruction."""
    return text.split(" = ", 1)[0]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def host_window(events: dict, span: str) -> Optional[Interval]:
    """Start and end (ns) of the first-to-last host span named ``span``
    extended to the end of the last one, or None."""
    hits = [(s, s + d) for name, s, d in events["host"] if name == span]
    if not hits:
        return None
    return min(s for s, _ in hits), max(e for _, e in hits)


def _label_gap(host: Sequence, lo: float, hi: float) -> str:
    """Name of the innermost benchmark span covering the gap's middle."""
    mid = 0.5 * (lo + hi)
    covering = [(d, name) for name, s, d in host if s <= mid <= s + d]
    return min(covering)[1] if covering else "no benchmark span"


def _overlap(spans: Sequence[Interval], cut: Sequence[Interval]) -> float:
    """Length of the part of the (merged) ``spans`` inside ``cut``."""
    return sum(max(0.0, min(e, ce) - max(s, cs))
               for s, e in spans for cs, ce in cut)


def reduce_events(events: dict, window: Interval,
                  exclude: Sequence[Interval] = (), top: int = 10) -> dict:
    """Per-device busy time inside ``window`` (ns interval), averaged over
    the devices, plus the breakdown (the idle gaps are the first
    device's).

    Busy is the union of the intervals in which an operation ran; the
    idle share is one minus busy over the window. ``collective_s`` is
    the device time of all-reduce operations, averaged over devices.
    ``exclude`` (compilation inside the window) is left out of both the
    window and the busy time; an idle gap inside it is labelled
    ``compilation``.
    """
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty trace window {window}")
    cut = union(clip(exclude, lo, hi))
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy, collective = [], []
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    for plane in sorted(devices):
        merged = union(clip([(s, s + d) for _, s, d in devices[plane]],
                            lo, hi))
        busy.append(sum(e - s for s, e in merged) - _overlap(merged, cut))
        coll = 0.0
        for name, s, d in devices[plane]:
            inside = min(s + d, hi) - max(s, lo)
            if inside <= 0:
                continue
            op_time[name] = op_time.get(name, 0.0) + inside
            if any(m in name.lower() for m in COLLECTIVE_MARKERS):
                coll += inside
        collective.append(coll)
        if not gaps:       # the idle gaps of the first device
            edges = [lo] + [t for se in merged for t in se] + [hi]
            gaps = [(g_hi - g_lo,
                     "compilation" if _overlap([(g_lo, g_hi)], cut) > 0
                     else _label_gap(events["host"], g_lo, g_hi))
                    for g_lo, g_hi in zip(edges[0::2], edges[1::2])
                    if g_hi > g_lo]
    n_dev = len(devices)
    window_s = ((hi - lo) - sum(e - s for s, e in cut)) * 1e-9
    busy_s = sum(busy) / n_dev * 1e-9
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[0])
    return {
        "n_devices": n_dev,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "collective_s": sum(collective) / n_dev * 1e-9,
        "breakdown": {
            "device_ops": [[name, t / n_dev * 1e-9] for name, t in ops],
            "idle_gaps": [[name, t * 1e-9] for t, name in gaps[:top]],
        },
    }
