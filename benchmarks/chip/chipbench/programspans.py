"""The program's own spans in a profiler trace, reduced.

The serving engine marks the phases of a request with
``jax.profiler.TraceAnnotation`` spans named ``dpmm.*``
(``repro/serve/dpmm.py``), its counts as the spans' arguments, on the
clock of the device's operations. ``load_program`` reads them from the
host plane of the newest ``.xplane.pb`` under a trace directory; the
benchmark's own ``bench.*`` spans stay ``tracereduce.load_events``'s.
``reduce_program`` gives, per span name, the count, total and self time
(the span less its child spans on the same thread) and the sums of the
numeric arguments; ``engine_idle`` the device's idle time inside the
engine's request spans, by the innermost span covering it;
``engine_readings`` the serving engine's numbers from both. Every
function takes plain lists, so the arithmetic is tested on hand-made
spans.
"""
from __future__ import annotations

import bisect
import glob
import itertools
import os
import warnings
from typing import Dict, List, Optional, Sequence

from chipbench.tracereduce import Interval, clip, union

PROGRAM_SPAN_PREFIX = "dpmm."
REQUEST_SPAN = "dpmm.serve.query"


def load_program(trace_dir: str) -> list:
    """``[[span, start_ns, dur_ns, {arg: value}, thread], ...]``: the
    host plane's ``dpmm.*`` events of the newest ``.xplane.pb`` under
    ``trace_dir``; ``thread`` names the plane's line the event is on."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    program = []
    with warnings.catch_warnings():
        # iterating an event's stats warns of the binding's own type
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for i, line in enumerate(plane.lines):
                program.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns),
                     dict(e.stats), f"{plane.name}/{i}"]
                    for e in line.events
                    if e.name.startswith(PROGRAM_SPAN_PREFIX))
    return program


def _end(event) -> float:
    return event[1] + event[2]


def _less(interval: Interval, cut: Sequence[Interval]) -> List[Interval]:
    """``interval`` less the sorted, merged ``cut``."""
    lo, hi = interval
    out, at = [], lo
    for s, e in cut:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def own_time(program: Sequence, within: Optional[str] = None):
    """For each span, the intervals of its own time (the span less its
    children: the spans nested in it on the same thread) and whether it
    is a ``within`` span or nested in one (every span when ``within`` is
    None)."""
    own: List[List[Interval]] = [[] for _ in program]
    inside = [within is None] * len(program)
    threads: Dict[object, List[int]] = {}
    for i, event in enumerate(program):
        threads.setdefault(event[4] if len(event) > 4 else None,
                           []).append(i)
    for idx in threads.values():
        idx.sort(key=lambda i: (program[i][1], -program[i][2]))
        children: Dict[int, List[Interval]] = {i: [] for i in idx}
        stack: List[int] = []
        for i in idx:
            while stack and _end(program[stack[-1]]) <= program[i][1]:
                stack.pop()
            if stack:
                children[stack[-1]].append((program[i][1], _end(program[i])))
                inside[i] = inside[i] or inside[stack[-1]]
            inside[i] = inside[i] or program[i][0] == within
            stack.append(i)
        for i in idx:
            lo, hi = program[i][1], _end(program[i])
            own[i] = _less((lo, hi), union(clip(children[i], lo, hi)))
    return own, inside


def reduce_program(program: Sequence, window: Interval,
                   within: Optional[str] = None) -> Dict[str, dict]:
    """Per span name, over the spans that start inside ``window`` (ns)
    and, with ``within``, are such a span or nested in one: ``count``,
    ``total_s``, ``self_s``, ``args`` (each numeric argument's sum) and
    ``split_s`` (total seconds by each text argument's value, as
    ``"out=labels"``)."""
    lo, hi = window
    own, inside = own_time(program, within)
    out: Dict[str, dict] = {}
    for event, segments, keep in zip(program, own, inside):
        name, start, dur, args = event[:4]
        if not keep or not lo <= start < hi:
            continue
        r = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                  "self_s": 0.0, "args": {}, "split_s": {}})
        r["count"] += 1
        r["total_s"] += dur * 1e-9
        r["self_s"] += sum(e - s for s, e in segments) * 1e-9
        for key, value in args.items():
            if isinstance(value, bool):
                continue
            if isinstance(value, (int, float)):
                r["args"][key] = r["args"].get(key, 0) + value
            else:
                tag = f"{key}={value}"
                r["split_s"][tag] = r["split_s"].get(tag, 0.0) + dur * 1e-9
    return out


def _measure(intervals: Sequence[Interval]):
    """``(s, e) -> `` the length of the part of the sorted, disjoint
    ``intervals`` inside ``(s, e)``, by bisection."""
    starts = [s for s, _ in intervals]
    ends = [e for _, e in intervals]
    acc = list(itertools.accumulate((e - s for s, e in intervals),
                                    initial=0.0))

    def before(t: float) -> float:
        j = bisect.bisect_right(starts, t)
        return acc[j] - max(0.0, ends[j - 1] - t) if j else 0.0
    return lambda s, e: before(e) - before(s)


def engine_idle(devices: Dict[str, list], program: Sequence,
                window: Interval, exclude: Sequence[Interval] = (),
                within: str = REQUEST_SPAN) -> dict:
    """The device's idle time inside ``within`` spans, over ``window``
    (ns), averaged over the device planes as ``tracereduce.reduce_events``
    averages busy time: ``idle_s``, and ``by_span_s``, the same time by
    the innermost program span covering it. Idle is the window less the
    union of the plane's operations and of ``exclude`` (compilation)."""
    lo, hi = window
    if not devices:
        raise ValueError("the trace holds no device operations")
    cut = union(clip(exclude, lo, hi))
    own, inside = own_time(program, within)
    segments = [(event[0], s, e)
                for event, mine, keep in zip(program, own, inside) if keep
                for s, e in clip(mine, lo, hi)]
    by_span: Dict[str, float] = {}
    for plane in sorted(devices):
        busy = union(clip([(s, s + d) for _, s, d in devices[plane]], lo, hi)
                     + cut)
        idle = _measure(_less((lo, hi), busy))
        for name, s, e in segments:
            by_span[name] = by_span.get(name, 0.0) + idle(s, e)
    n = len(devices)
    return {"idle_s": sum(by_span.values()) / n * 1e-9,
            "by_span_s": {name: t / n * 1e-9
                          for name, t in sorted(by_span.items())}}


def engine_readings(spans: Dict[str, dict], idle: dict, requests: int,
                    window_s: float) -> Optional[Dict[str, dict]]:
    """The serving engine's numbers from ``reduce_program(...,
    within=REQUEST_SPAN)`` and ``engine_idle`` over a window of
    ``window_s`` in which ``requests`` were answered, or None where the
    trace holds no request span:

    - ``serve_engine_ms``: the request spans' total per request answered;
      ``self_ms``, each span name's self time per request, adds up to it;
    - ``serve_copy_back_ms``: the copies to the host per request, with
      their bytes and the time by output;
    - ``engine_idle_pct``: the device's idle time inside request spans
      over the window, with the idle ms by innermost span;
    - ``serve_pad_efficiency``: rows answered over rows dispatched.
    """
    query = spans.get(REQUEST_SPAN)
    if not query or requests <= 0 or window_s <= 0:
        return None
    per = 1e3 / requests
    copy = spans.get("dpmm.serve.copy_back", {"total_s": 0.0, "args": {},
                                              "split_s": {}})
    segment = spans.get("dpmm.serve.segment", {"args": {}})
    used, batch = (segment["args"].get(k, 0) for k in ("used", "batch"))
    return {
        "serve_engine_ms": {
            "value": query["total_s"] * per,
            "self_ms": {name: r["self_s"] * per
                        for name, r in sorted(spans.items())}},
        "serve_copy_back_ms": {
            "value": copy["total_s"] * per,
            "bytes_per_request": copy["args"].get("bytes", 0) / requests,
            "by_out_ms": {tag.split("=", 1)[1]: t * per
                          for tag, t in sorted(copy["split_s"].items())
                          if tag.startswith("out=")}},
        "engine_idle_pct": {
            "value": 100.0 * idle["idle_s"] / window_s,
            "by_span": {name: 1e3 * t
                        for name, t in idle["by_span_s"].items()}},
        "serve_pad_efficiency": {
            "value": 100.0 * used / batch if batch else 0.0},
    }
