"""One run of a serving cell: the program's assignment engine under an open
loop of requests.

The served model is made from the seed by the benchmark
(``served/<family>.py``: the mixture the configuration's generator draws
its points from) and handed to the program's engine
(``repro.serve.DPMMEngine``, every knob at its default). Requests come at
the mix's fixed rate, whatever the engine does: each seed gets the same
multiset of request sizes (log-uniform over ``rows_min``..``rows_max``)
and of gaps between arrivals (exponential quantiles at ``rate_rps``), in
an order of its own, and its own rows from a pool of points drawn from
the same mixture. One process, one thread: a request is served when it
is due or, if the engine is still busy, as soon as it is free, and its
latency is counted from the time it was due, so queueing counts.

``serve_p95_ms`` is the 95th percentile of every request's latency;
``setup_s`` runs from process start to the first request's due time
(data, model, the engine's build and compilation, one warm query per
step size). After the window a sample of requests drawn from the seed,
the longest among them, is compared with the family's reference
(``evaluate``).
"""
from __future__ import annotations

import contextlib
import gc
import math
import shutil
import tempfile
import time
from typing import Callable, Dict, Optional

import numpy as np

from chipbench import check, datagen, programspans, spec, tracereduce

KIND = "serve_open_loop"
SPIN_S = 1e-3              # the last stretch of a wait spins: a sleeping
#                            process can wake late on a busy host
REF_BLOCK = 8192


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def derive_seeds(seed: int):
    """(data seed, traffic seed) from any whole number, however large."""
    data, traffic = np.random.SeedSequence([seed, 7]).generate_state(2)
    return int(data), int(traffic)


def schedule(mix: dict, seconds: float, traffic_seed: int) -> dict:
    """The requests of a window of ``seconds``: due time (s after the
    window's start), rows, offset into the pool, and the requests whose
    answers are compared."""
    rate = float(mix["rate_rps"])
    m = max(1, int(round(rate * seconds)))
    q = (np.arange(m) + 0.5) / m
    lo, hi = int(mix["rows_min"]), int(mix["rows_max"])
    sizes = np.floor(np.exp(math.log(lo) + q * (math.log(hi + 1)
                                                - math.log(lo))))
    sizes = np.clip(sizes, lo, hi).astype(np.int64)
    gaps = -np.log1p(-q) / rate
    rng = np.random.default_rng(traffic_seed)
    sizes, gaps = rng.permutation(sizes), rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    offsets = rng.integers(0, int(mix["pool_rows"]) - sizes + 1)
    picked = rng.choice(m, size=min(m, int(mix["check_requests"])),
                        replace=False)
    check = sorted(set(picked.tolist()) | {int(np.argmax(sizes))})
    return {"due": due, "gaps": gaps, "rows": sizes, "offsets": offsets,
            "check": check}


def prepare(cell: dict, seed: int) -> dict:
    """Everything before the window: the pool of query points, the
    served model, the engine, and one warm query per step size."""
    from repro.serve import DPMMEngine, ServeConfig
    config, mix = cell["config"], cell["mix"]
    family = config["family"]
    data_seed, traffic_seed = derive_seeds(seed)
    ref = spec.load_module("reference", family)
    with span("bench.datagen"):
        pool, _ = datagen.generate(dict(config["data"],
                                        n=int(mix["pool_rows"])), data_seed)
        served = spec.load_module("served", family).build(config, data_seed,
                                                          ref)
    with span("bench.engine_build"):
        engine = DPMMEngine(served["state"], config["dpmm"]["component"],
                            ServeConfig())
    with span("bench.warm_up"):
        for b in engine.batch_sizes:
            engine.query(pool[:b])
    return {"pool": pool, "served": served, "engine": engine, "ref": ref,
            "traffic_seed": traffic_seed}


def open_loop(engine, pool: np.ndarray, sched: dict,
              clock: Callable[[], float] = time.perf_counter) -> dict:
    """Serve every request of ``sched`` at its due time or after; keep the
    answers of the requests to compare."""
    due, rows, offsets = sched["due"], sched["rows"], sched["offsets"]
    check = set(sched["check"])
    latency = np.full(len(due), np.nan)
    kept: Dict[int, tuple] = {}
    unanswered = 0
    # set-up's objects move out of the collector's reach, so that a full
    # collection inside the window walks only what the window allocates
    gc.collect()
    gc.freeze()
    with span("bench.serve_window"):
        wall0, t0 = time.time(), clock()
        for i in range(len(due)):
            at = t0 + due[i]
            wait = at - clock()
            if wait > 0:
                with span("bench.wait"):
                    if wait > SPIN_S:
                        time.sleep(wait - SPIN_S)
                    while clock() < at:
                        pass
            lo = int(offsets[i])
            try:
                with span("bench.request"):
                    res = engine.query(pool[lo:lo + int(rows[i])])
            except Exception:              # an answer that never comes
                unanswered += 1
                continue
            latency[i] = clock() - at
            if i in check:
                kept[i] = (np.array(res.labels), np.array(res.log_predictive))
        t_end = clock()
    return {"latency_s": latency, "kept": kept, "unanswered": unanswered,
            "window_s": t_end - t0, "wall_start": wall0,
            "wall_end": wall0 + (t_end - t0)}


def reference_scores(ref, served: dict, x: np.ndarray, dtype=None):
    """(n, k) log weight plus log density of each row under each served
    cluster: the reference at float32 ``highest``, or all in ``dtype``."""
    import jax
    import jax.numpy as jnp
    params = {k: jnp.asarray(v) for k, v in served["params"].items()}
    logw = jnp.asarray(served["logw"], jnp.float32)
    logdet = jnp.asarray(served["logdet"], jnp.float32)
    n, d = x.shape
    pad = (-n) % REF_BLOCK
    xb = np.concatenate([x, np.zeros((pad, d), x.dtype)]).reshape(
        -1, REF_BLOCK, d)
    if dtype is None:
        one = lambda b: ref.logp(b, logw, params, logdet)
    else:
        one = lambda b: ref.logp_in(b, logw, params, logdet, dtype)
    out = jax.jit(lambda xb: jax.lax.map(one, xb))(jnp.asarray(xb))
    return np.asarray(out, np.float64).reshape(-1, len(served["slots"]))[:n]


def answers(pool: np.ndarray, sched: dict, kept: dict):
    """The compared requests' rows and the engine's answers, stacked."""
    order = sorted(kept)
    x = np.concatenate([pool[int(sched["offsets"][i]):
                             int(sched["offsets"][i]) + int(sched["rows"][i])]
                        for i in order])
    labels = np.concatenate([kept[i][0] for i in order])
    logpred = np.concatenate([kept[i][1] for i in order])
    return x, labels, logpred


def evaluate(lp: np.ndarray, served: dict, labels: np.ndarray,
             logpred: np.ndarray, unanswered: int) -> Dict[str, float]:
    """The numbers compared, for answers (``labels`` as dense slots,
    ``logpred`` the log predictive density) against the reference's
    scores ``lp``. ``answer_gap``, in nats of the reference, is the
    larger of the widest gap by which an answer's label lies below the
    reference's best (``label_gap``) and the mean gap between the
    answers' log predictive densities and the reference's
    (``logpred_mean``); ``logpred_gap`` is that gap's widest."""
    from scipy.special import logsumexp
    k_max, slots = served["k_max"], served["slots"]
    index = np.full((k_max,), -1)
    index[slots] = np.arange(len(slots))
    pos = index[np.clip(labels, 0, k_max - 1)]
    good = ((labels >= 0) & (labels < k_max) & (pos >= 0)
            & np.isfinite(logpred))
    ref_pred = logsumexp(lp, axis=1)
    chosen = np.take_along_axis(lp, np.maximum(pos, 0)[:, None], 1)[:, 0]
    label_gap = np.where(good, lp.max(axis=1) - chosen, 0.0)
    pred_gap = np.where(good, np.abs(logpred - ref_pred), 0.0)
    return {"unanswered": float(unanswered),
            "bad_answers": float(np.sum(~good)),
            "answer_gap": float(max(label_gap.max(), pred_gap.mean())),
            "label_gap": float(label_gap.max()),
            "logpred_mean": float(pred_gap.mean()),
            "logpred_gap": float(pred_gap.max()),
            "checked_rows": float(len(labels))}


def control_answers(lp_low: np.ndarray, served: dict):
    """The answers the reference gives in lower precision: its argmax
    slot and its log predictive density, computed in that precision."""
    import jax.numpy as jnp
    from jax.scipy.special import logsumexp
    low = jnp.asarray(lp_low).astype(jnp.bfloat16)
    labels = served["slots"][np.asarray(jnp.argmax(low, axis=1))]
    logpred = np.asarray(logsumexp(low, axis=1).astype(jnp.float32))
    return labels, logpred.astype(np.float64)


def engine_context(events: dict, program: list, loop: dict,
                   compile_spans) -> dict:
    """The traced serving window reduced: the device's busy time
    (``trace``) on the ``chips`` the engine used, less compilation; every
    ``dpmm.*`` span starting in the window (``program``); and the serving
    engine's readings from its request spans (``engine``, None where
    there is none)."""
    window = tracereduce.host_window(events, "bench.serve_window")
    if window is None:
        raise RuntimeError("the trace holds no serving window span")
    # the engine serves from one chip; a chip it never touched is not
    # part of its busy time
    used = {plane: ops for plane, ops in events["devices"].items() if ops}
    offset = window[0] - loop["wall_start"] * 1e9
    exclude = [(s * 1e9 + offset, e * 1e9 + offset)
               for s, e in compile_spans]
    trace = tracereduce.reduce_events(dict(events, devices=used), window,
                                      exclude)
    requests = int(np.sum(np.isfinite(loop["latency_s"])))
    in_requests = programspans.reduce_program(program, window,
                                              programspans.REQUEST_SPAN)
    idle = programspans.engine_idle(used, program, window, exclude)
    return {"trace": trace, "chips": len(used),
            "program": programspans.reduce_program(program, window),
            "engine": programspans.engine_readings(
                in_requests, idle, requests, trace["window_s"])}


def trace_context(cell: dict, events: dict, program: list, loop: dict,
                  sched: dict, served: dict, device_kind: str,
                  compile_spans) -> dict:
    """The per-layer readers' input: ``engine_context`` and the work of
    the rows answered, with the chip's peaks."""
    counts = spec.load_module("counts", cell["config"]["family"])
    rows = int(np.sum(sched["rows"][np.isfinite(loop["latency_s"])]))
    return dict(engine_context(events, program, loop, compile_spans),
                query_work=counts.query_work(rows, served["d"],
                                             len(served["slots"]),
                                             served["k_max"]),
                peaks=spec.peaks(device_kind))


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             t_process: float, prep: Optional[dict] = None,
             say=print) -> dict:
    """One run of a serving cell; returns the result line's object (with
    ``_rows``, the compared numbers, for the caller to print). Tests pass
    a ``prep`` made by ``prepare``."""
    import jax
    from chipbench.window import WindowRecorder
    config, mix = cell["config"], cell["mix"]
    prep = prep or prepare(cell, seed)
    length = float(mix["trace_seconds"]) if trace else seconds
    sched = schedule(mix, length, prep["traffic_seed"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace \
        else None
    try:
        profile = (jax.profiler.trace(trace_dir) if trace
                   else contextlib.nullcontext())
        setup_s = time.time() - t_process
        with WindowRecorder(wrap_calls=False) as rec, profile:
            loop = open_loop(prep["engine"], prep["pool"], sched)
        n_comp, n_trace, compile_s, compile_spans = rec.compiles_in(
            loop["wall_start"], loop["wall_end"])
        memory = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0))
                     for d in devices[:cell["chips"]])
        lat = loop["latency_s"][np.isfinite(loop["latency_s"])]
        rows_done = int(np.sum(sched["rows"][np.isfinite(
            loop["latency_s"])]))
        say(f"window: {len(sched['due'])} requests at {mix['rate_rps']} "
            f"per s, {rows_done} rows in {loop['window_s']:.6f} s "
            f"({rows_done / loop['window_s']:.1f} rows/s); latency ms p50 "
            f"{1e3 * np.percentile(lat, 50):.4f} p95 "
            f"{1e3 * np.percentile(lat, 95):.4f} p99 "
            f"{1e3 * np.percentile(lat, 99):.4f} max "
            f"{1e3 * lat.max():.4f}; compilations in the window {n_comp} "
            f"(traces {n_trace}, {compile_s:.6f} s); K served "
            f"{len(prep['served']['slots'])} of {prep['served']['k_max']}; "
            f"peak_bytes_in_use {memory}")
        context = None
        if trace:
            context = trace_context(
                cell, tracereduce.load_events(trace_dir),
                programspans.load_program(trace_dir), loop, sched,
                prep["served"], devices[0].device_kind, compile_spans)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    x, labels, logpred = answers(prep["pool"], sched, loop["kept"])
    served, ref = prep["served"], prep["ref"]
    del prep                      # the engine's device state goes first
    lp = reference_scores(ref, served, x)
    numbers = evaluate(lp, served, labels, logpred, loop["unanswered"])
    rows = check.verdict(numbers, config["limits"][KIND])
    say(f"checked {int(numbers['checked_rows'])} rows of "
        f"{len(loop['kept'])} requests; readings {numbers}")
    values = {"serve_p95_ms": 1e3 * float(np.percentile(lat, 95)),
              "setup_s": setup_s}
    return check.result_line(cell, rows, int(len(sched["due"])),
                             int(loop["unanswered"]), devices, memory,
                             context, values)
