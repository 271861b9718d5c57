#!/usr/bin/env python3
"""The highest request rate a serving cell's engine sustains on the chip:
the cell's open loop at a ladder of offered rates, in one process.

    python3 benchmarks/chip/knee.py --workload <serving cell> --seed <n> \\
        --rates 100 200 400 --seconds 10

For each rate it prints one JSON line: the offered and the completed
requests per second, rows per second, the latency percentiles, and how
long after the last request was due the engine finished (the backlog at
the close). A rate is sustained when the engine completes 99% of it and
its 99th percentile stays within ten times the lowest rate's, so the
queue stays bounded. The ladder stops at the first rate whose backlog
passes two seconds; seven rates between the highest sustained one and
the next follow, and the last line names the highest sustained rate
and four fifths of it, the mix's ``rate_rps``. The benchmark's own runs
never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run
from chipbench import serve, spec

STOP_BACKLOG_S = 2.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run.configure_cache()
    try:
        run.require_chips(cell["chips"])
    except run.NoChip as e:
        print(f"knee.py: {e}", file=sys.stderr)
        return 3
    prep = serve.prepare(cell, args.seed)
    seen = sweep(cell, prep, args.rates, args.seconds)
    knee = highest_sustained(seen)
    above = min((r["offered_rps"] for r in seen
                 if r["offered_rps"] > knee), default=2 * knee)
    seen += sweep(cell, prep, [knee + f * (above - knee) / 8
                               for f in range(1, 8)], args.seconds)
    knee = highest_sustained(seen)
    print(json.dumps({"sustained_rps": knee, "rate_rps": 0.8 * knee}))
    return 0


def sweep(cell: dict, prep: dict, rates, seconds: float) -> list:
    seen = []
    for rate in rates:
        mix = dict(cell["mix"], rate_rps=rate)
        sched = serve.schedule(mix, seconds, prep["traffic_seed"])
        loop = serve.open_loop(prep["engine"], prep["pool"], sched)
        lat = 1e3 * loop["latency_s"]
        backlog = loop["window_s"] - float(sched["due"][-1])
        seen.append({
            "offered_rps": rate, "requests": int(len(lat)),
            "completed_rps": len(lat) / loop["window_s"],
            "rows_per_s": int(sched["rows"].sum()) / loop["window_s"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "backlog_s": backlog})
        print(json.dumps(seen[-1]), flush=True)
        if backlog > STOP_BACKLOG_S:
            break                       # past the knee: higher rates wait
    return seen


def highest_sustained(seen: list) -> float:
    base = min(seen, key=lambda r: r["offered_rps"])["p99_ms"]
    return max(r["offered_rps"] for r in seen
               if r["completed_rps"] >= 0.99 * r["offered_rps"]
               and r["p99_ms"] <= 10 * base)


if __name__ == "__main__":
    sys.exit(main())
