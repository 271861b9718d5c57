#!/usr/bin/env python3
"""Readings of the comparison that decides ``correct``: the program's and
its control's, over many seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \\
        [--seconds 4] [--out chiprun_out/control_<cell>.jsonl]

For each seed it runs the cell's set-up and a short window on the chip,
then reads every compared number twice on the same outputs: once as the
program produced them, once with the control in the program's place. In
a fit cell (a burn-in, then a whole number of chunks lasting about
``--seconds``) the control is the reference's statistics folded in
bfloat16 and the drawn parameters held in bfloat16
(``check.control_outputs``); in a serving cell (``--seconds`` of the
cell's open loop) it is the reference computed in bfloat16 answering the
same requests (``serve.control_answers``). The limits in the
configuration are set from these readings (see ``PERF.md``). The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run
from chipbench import check, serve, spec


def readings(cell: dict, seed: int, seconds: float, devices) -> dict:
    prep = run.prepare(cell, seed)
    iters = run.window_iters(prep, cell["mix"], seconds, trace=False)
    win = run.measure(prep, iters)
    ref = spec.load_module("reference", cell["config"]["family"])
    out = check.program_outputs(win["result"].state, win["point"],
                                len(win["result"].history["k"]), ref)
    it_start = int(np.asarray(prep["burned"].state.it))
    x, config = prep["x"], cell["config"]
    del prep, win
    program = check.evaluate(ref, x, out, config, iters, it_start)
    control = check.evaluate(ref, x, check.control_outputs(ref, x, out),
                             config, iters, it_start)
    return {"seed": seed, "iters": iters, "program": program,
            "control": control}


def serve_readings(cell: dict, seed: int, seconds: float, devices) -> dict:
    import jax.numpy as jnp
    prep = serve.prepare(cell, seed)
    sched = serve.schedule(cell["mix"], seconds, prep["traffic_seed"])
    loop = serve.open_loop(prep["engine"], prep["pool"], sched)
    x, labels, logpred = serve.answers(prep["pool"], sched, loop["kept"])
    served, ref = prep["served"], prep["ref"]
    del prep
    lp = serve.reference_scores(ref, served, x)
    program = serve.evaluate(lp, served, labels, logpred,
                             loop["unanswered"])
    low = serve.reference_scores(ref, served, x, jnp.bfloat16)
    control = serve.evaluate(lp, served, *serve.control_answers(low, served),
                             0)
    return {"seed": seed, "requests": int(len(sched["due"])),
            "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run.configure_cache()
    try:
        devices = run.require_chips(cell["chips"])
    except run.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 3
    sink = open(args.out, "a") if args.out else None
    try:
        for seed in args.seeds:
            read = (serve_readings if cell["mix"]["kind"] == serve.KIND
                    else readings)
            row = read(cell, seed, args.seconds, devices)
            text = json.dumps(row)
            print(text, flush=True)
            if sink:
                sink.write(text + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
