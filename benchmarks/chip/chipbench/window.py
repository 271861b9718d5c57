"""Instrumentation of the timed window, from the benchmark's side.

The fit driver runs each chunk of iterations as an AOT-compiled program
(``jax.stages.Compiled``) and pulls the chunk's history to the host
before it dispatches the next. ``WindowRecorder`` wraps
``Compiled.__call__`` while it is active: it records when each chunk was
dispatched, keeps the last chunk's outputs (the state the timed path
produced), and puts a ``bench.chunk_dispatch`` span into the profiler's
trace. It also collects every trace, lowering and compilation span that
``jax.monitoring`` reports, so that the window can leave compilation out
and count it.
"""
from __future__ import annotations

import time
from typing import Any, List, Optional, Tuple

import jax

from chipbench.tracereduce import union

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT)

_recorders: List["WindowRecorder"] = []
_listening = False


def _on_span(event: str, start: float, end: float, **_: Any) -> None:
    if event in COMPILE_EVENTS:
        for rec in _recorders:
            rec.compile_spans.append((event, start, end))


def _is_chunk(out: Any) -> bool:
    """A resident chunk returns ``((model, point), history)``, the
    history a dict of per-iteration traces that holds ``"k"``."""
    return (isinstance(out, tuple) and len(out) == 2
            and isinstance(out[1], dict) and "k" in out[1]
            and isinstance(out[0], tuple) and len(out[0]) == 2)


class WindowRecorder:
    """Context manager; ``calls`` holds the wall-clock (``time.time``)
    dispatch time of every chunk, ``last`` the newest chunk's
    ``(model, point)``. With ``wrap_calls=False`` it only collects the
    compilation spans."""

    def __init__(self, wrap_calls: bool = True) -> None:
        self.calls: List[float] = []
        self.last: Optional[Tuple[Any, Any]] = None
        self.compile_spans: List[Tuple[str, float, float]] = []
        self._wrap = wrap_calls
        self._orig = None

    def __enter__(self) -> "WindowRecorder":
        global _listening
        if not _listening:
            jax.monitoring.register_event_time_span_listener(_on_span)
            _listening = True
        _recorders.append(self)
        if not self._wrap:
            return self
        self._orig = orig = jax.stages.Compiled.__call__
        rec = self

        def call(compiled, *args, **kwargs):
            t = time.time()
            with jax.profiler.TraceAnnotation("bench.chunk_dispatch"):
                out = orig(compiled, *args, **kwargs)
            if _is_chunk(out):
                rec.calls.append(t)
                rec.last = out[0]
            return out

        jax.stages.Compiled.__call__ = call
        return self

    def __exit__(self, *exc) -> None:
        if self._wrap:
            jax.stages.Compiled.__call__ = self._orig
        _recorders.remove(self)

    def start(self) -> float:
        """Wall-clock start of the window: the first chunk's dispatch."""
        if not self.calls:
            raise RuntimeError(
                "the fit dispatched no compiled chunk: the window cannot "
                "be located")
        return self.calls[0]

    def compiles_in(self, start: float, end: float):
        """(backend compilations, traces, seconds covered by any trace,
        lowering or compilation span, those spans) inside [start, end].
        A compilation served from the persistent cache counts too."""
        inside = [(e, s, t) for e, s, t in self.compile_spans
                  if s >= start and t <= end]
        n_comp = sum(e == COMPILE_EVENT for e, _, _ in inside)
        n_trace = sum(e == TRACE_EVENT for e, _, _ in inside)
        spans = union([(s, t) for _, s, t in inside])
        return n_comp, n_trace, sum(e - s for s, e in spans), spans
