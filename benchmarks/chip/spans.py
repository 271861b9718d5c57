#!/usr/bin/env python3
"""The serving engine's host time per phase of a request, read from its
own spans in a profiler trace of a serving cell's open loop.

    python3 benchmarks/chip/spans.py --workload <serving cell> \\
        --seeds 1 2 3 [--seconds 20] [--chips 1] [--out <file.jsonl>]

For each seed, in one process: the cell's set-up, a window of
``--seconds`` of its open loop with no profiler (the latency the cell
reports, and the engine's mean time per query on the host clock,
``engine_ms``), then a window of the mix's ``trace_seconds`` under
``jax.profiler.trace``, timed the same way. The traced window is reduced
as the cell's traced run reduces it (``serve.engine_context``): the
device's idle share, the engine's readings from its ``dpmm.*`` spans
(``serve_engine_ms`` with each span's self time per request,
``serve_copy_back_ms``, ``engine_idle_pct`` and
``serve_pad_efficiency``), and every span's count, times and argument
sums; ``outside_requests`` counts the spans that no request span holds.
One JSON line per seed. ``--chips`` asks for fewer chips than the
cell has (the engine serves from the first). The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time

import numpy as np

import run
from chipbench import programspans, serve, spec, tracereduce


class Timed:
    """The engine, each query's time on the host clock recorded."""

    def __init__(self, engine):
        self.engine, self.seconds = engine, []

    def query(self, x):
        t0 = time.perf_counter()
        try:
            return self.engine.query(x)
        finally:
            self.seconds.append(time.perf_counter() - t0)


def timed_loop(prep: dict, sched: dict) -> tuple:
    """The open loop over ``sched``, and its latency and the engine's
    mean time per query on the host clock (ms)."""
    timed = Timed(prep["engine"])
    loop = serve.open_loop(timed, prep["pool"], sched)
    lat = 1e3 * loop["latency_s"][np.isfinite(loop["latency_s"])]
    return loop, {"requests": int(lat.size), "mean_ms": float(lat.mean()),
                  "p50_ms": float(np.percentile(lat, 50)),
                  "p95_ms": float(np.percentile(lat, 95)),
                  "engine_ms": 1e3 * float(np.mean(timed.seconds))}


def traced(cell: dict, prep: dict) -> dict:
    """The mix's traced window, reduced as the cell's traced run reduces
    it."""
    import jax
    from chipbench.window import WindowRecorder
    sched = serve.schedule(cell["mix"], float(cell["mix"]["trace_seconds"]),
                           prep["traffic_seed"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench_spans_")
    try:
        with WindowRecorder(wrap_calls=False) as rec, \
                jax.profiler.trace(trace_dir):
            loop, host = timed_loop(prep, sched)
        n_comp, _, _, compile_spans = rec.compiles_in(loop["wall_start"],
                                                      loop["wall_end"])
        events = tracereduce.load_events(trace_dir)
        program = programspans.load_program(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = serve.engine_context(events, program, loop, compile_spans)
    engine = ctx["engine"]
    inside = engine["serve_engine_ms"]["self_ms"] if engine else {}
    return {"latency": host, "compiles_in_window": n_comp,
            "device_idle_pct.serve": ctx["trace"]["idle_pct"],
            "readings": engine, "spans": ctx["program"],
            "outside_requests": {name: r["count"]
                                 for name, r in ctx["program"].items()
                                 if name not in inside}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--chips", type=int, default=None)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell["mix"]["kind"] != serve.KIND:
        print(f"spans.py: {args.workload} is not a serving cell",
              file=sys.stderr)
        return 2
    run.configure_cache()
    try:
        run.require_chips(args.chips or cell["chips"])
    except run.NoChip as e:
        print(f"spans.py: {e}", file=sys.stderr)
        return 3
    for seed in args.seeds:
        prep = serve.prepare(cell, seed)
        sched = serve.schedule(cell["mix"], args.seconds,
                               prep["traffic_seed"])
        plain = timed_loop(prep, sched)[1]
        line = json.dumps({"seed": seed, "untraced": plain,
                           "traced": traced(cell, prep)})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as out:
                out.write(line + "\n")
        del prep
    return 0


if __name__ == "__main__":
    sys.exit(main())
