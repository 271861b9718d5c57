#!/usr/bin/env python3
"""On-chip benchmark of the DPMM sampler: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

A cell (``BENCHMARK.json``) is a configuration (``configs/<c>.json``: the
family, the data's shape and the sampler's settings) under a traffic mix
(``mixes/<t>.json``) on 1 or 4 chips. The mix's ``kind`` names how the
run drives the program: ``serve_open_loop`` (``chipbench/serve.py``)
queries the assignment engine at a fixed rate; ``fit_continuation``
(here) generates the data from ``--seed``, fits a burn-in through
``DPMM.fit`` (which compiles what the window uses), then times the
window: a continuation ``DPMM.fit(x, iters=M, init_state=burned.state)``,
M a whole number of the driver's chunks chosen from the burn-in's rate so
that the window lasts ``--seconds``. With ``--trace 1`` the window is
shorter, under the profiler, and the result carries the per-layer
metrics instead.

A fit's end-to-end metrics: ``iter_ms``, the window's wall time from the
first chunk's dispatch to the fit's return (the last chunk's result is
then on the host), less any compilation inside it, over the iterations
it ran; ``setup_s``, from process start to the start of the window.
After the window the run compares what it produced with the family's
plain reference (``chipbench/check.py``) and prints each number beside
its limit.

The last line of standard output is one JSON object. Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero before it
fits anything and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()        # set-up is counted from here

import argparse                # noqa: E402
import contextlib              # noqa: E402
import json                    # noqa: E402
import math                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402
import tempfile                # noqa: E402
from pathlib import Path       # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np             # noqa: E402

from chipbench import (check, datagen, programspans, serve, spec,  # noqa: E402
                       tracereduce)

FIT_KIND = "fit_continuation"


class NoChip(RuntimeError):
    pass


def derive_seeds(seed: int):
    """(data seed, chain seed) from any whole number, however large."""
    data, chain = np.random.SeedSequence(seed).generate_state(2)
    return int(data), int(chain) % (2 ** 31 - 1)


def configure_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory in the checkout, so that only
    the first run of a cell compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX found "
                     f"{len(devices)}")
    return devices


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def prepare(cell: dict, seed: int) -> dict:
    """Data from the seed and the burn-in fit: everything before the
    window."""
    from repro.configs import DPMMConfig
    from repro.core.distributed import make_data_mesh
    from repro.core.sampler import DPMM
    config, mix = cell["config"], cell["mix"]
    if mix["data_shards"] != cell["chips"]:
        raise ValueError(f"mix {cell['traffic']} shards the data over "
                         f"{mix['data_shards']} chips, the cell has "
                         f"{cell['chips']}")
    data_seed, chain_seed = derive_seeds(seed)
    with span("bench.datagen"):
        x, truth = datagen.generate(config["data"], data_seed)
    cfg = DPMMConfig(seed=chain_seed, iters=mix["burn_in_iters"],
                     **config["dpmm"])
    model = DPMM(cfg, mesh=make_data_mesh(mix["data_shards"]))
    with span("bench.burn_in"):
        burned = model.fit(x, iters=mix["burn_in_iters"])
    return {"x": x, "truth": truth, "cfg": cfg, "model": model,
            "burned": burned}


def window_iters(prep: dict, mix: dict, seconds: float, trace: bool) -> int:
    """A whole number of chunks: ``trace_chunks`` under the profiler,
    else enough, at the burn-in's last-chunk rate, to last ``seconds``."""
    chunk = prep["cfg"].log_every
    if trace:
        return mix["trace_chunks"] * chunk
    per_chunk = sum(prep["burned"].iter_times_s[-chunk:])
    n_chunks = max(mix["min_window_chunks"],
                   math.ceil(seconds / max(per_chunk, 1e-6)))
    return n_chunks * chunk


def measure(prep: dict, iters: int, trace_dir=None, window_patch=None):
    """The timed window. ``window_patch`` (tests only) is a context
    manager entered around the window's fit."""
    import jax
    from chipbench.window import WindowRecorder
    burned = prep["burned"]
    profile = (jax.profiler.trace(trace_dir) if trace_dir
               else contextlib.nullcontext())
    patch = window_patch or contextlib.nullcontext()
    with WindowRecorder() as rec, patch, profile, span("bench.window_fit"):
        result = prep["model"].fit(prep["x"], iters=iters,
                                   init_state=burned.state)
        end = time.time()
    start = rec.start()
    n_compiles, n_traces, compile_s, spans = rec.compiles_in(start, end)
    return {"result": result, "point": rec.last[1], "start": start,
            "end": end, "compiles": n_compiles, "traces": n_traces,
            "compile_s": compile_s, "compile_spans": spans}


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def trace_context(cell, trace_dir, win, iters, k_mean, device_kind):
    """The per-layer readers' input: the reduced trace, the program's
    ``dpmm.*`` spans in the window by name, and the counts."""
    events = tracereduce.load_events(trace_dir)
    dispatch = tracereduce.host_window(events, "bench.chunk_dispatch")
    fit = tracereduce.host_window(events, "bench.window_fit")
    if dispatch is None or fit is None:
        raise RuntimeError("the trace holds no chunk dispatch span")
    window = (dispatch[0], fit[1])
    # the trace's clock against the wall clock, anchored at the first
    # chunk's dispatch, places compilation inside the window on it
    offset = dispatch[0] - win["start"] * 1e9
    exclude = [(s * 1e9 + offset, e * 1e9 + offset)
               for s, e in win["compile_spans"]]
    data = cell["config"]["data"]
    counts = spec.load_module("counts", cell["config"]["family"])
    return {"trace": tracereduce.reduce_events(events, window, exclude),
            "program": programspans.reduce_program(
                programspans.load_program(trace_dir), window),
            "work": counts.work(data["n"], data["d"], k_mean),
            "peaks": spec.peaks(device_kind), "chips": cell["chips"],
            "iters_traced": iters}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices, window_patch=None, prep=None, say=print) -> dict:
    """One run of ``cell``; returns the result line's object. Tests pass
    a ``prep`` made once by ``prepare`` to time several windows."""
    config, mix = cell["config"], cell["mix"]
    prep = prep or prepare(cell, seed)
    iters = window_iters(prep, mix, seconds, trace)
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace \
        else None
    try:
        win = measure(prep, iters, trace_dir, window_patch)
        result = win["result"]
        ks = np.asarray(result.history["k"]).reshape(-1)
        used = devices[:cell["chips"]]
        memory = peak_bytes(used)
        window_s = win["end"] - win["start"]
        timed_s = window_s - win["compile_s"]
        setup_s = win["start"] - T_PROCESS
        say(f"window: {iters} iterations, sweep_paths="
            f"{dict(result.sweep_paths)}, K over the window "
            f"{ks.tolist()}, compilations in the window "
            f"{win['compiles']} (traces {win['traces']}, "
            f"{win['compile_s']:.6f} s left out), peak_bytes_in_use "
            f"{memory}, window wall {window_s:.6f} s against "
            f"sum(iter_times_s) {sum(result.iter_times_s):.6f} s")
        context = None
        if trace:
            context = trace_context(cell, trace_dir, win, iters,
                                    float(ks.mean()),
                                    devices[0].device_kind)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ref = spec.load_module("reference", config["family"])
    out = check.program_outputs(result.state, win["point"], len(ks), ref)
    it_start = int(np.asarray(prep["burned"].state.it))
    x = prep["x"]
    del prep, win, result                 # free the program's device state
    numbers = check.evaluate(ref, x, out, config, iters, it_start)
    rows = check.verdict(numbers, config["limits"][FIT_KIND])
    say(f"checked {int(numbers['checked_points'])} points of untouched "
        f"clusters; readings {json.dumps(numbers)}")
    values = {"iter_ms": 1e3 * timed_s / iters, "setup_s": setup_s}
    return check.result_line(cell, rows, iters, int(numbers["iters_short"]),
                             devices, memory, context, values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        import repro  # noqa: F401  (the system under test)
    except (OSError, KeyError, ImportError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    configure_cache()
    try:
        devices = require_chips(cell["chips"])
    except NoChip as e:
        print(f"run.py: {e}; no result", file=sys.stderr)
        return 3
    say = lambda m: print(m, flush=True)
    if cell["mix"]["kind"] == serve.KIND:
        line = serve.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, T_PROCESS, say=say)
    elif cell["mix"]["kind"] == FIT_KIND:
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        devices, say=say)
    else:
        print(f"run.py: no driver for traffic kind "
              f"{cell['mix']['kind']!r}", file=sys.stderr)
        return 2
    rows = line.pop("_rows")
    print(json.dumps(line), flush=True)
    for name, value, limit, ok in rows:
        print(f"check {name} {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
