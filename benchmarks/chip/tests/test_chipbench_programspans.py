"""The serving engine's own spans: their reduction on hand-made spans
whose answers are counted by hand, their reading from a recorded trace,
the engine's spans on the CPU, where every count is known from the
requests sent, and their way into the cells' per-layer readers."""
import contextlib
import sys
import tempfile
from pathlib import Path
from unittest import mock

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from chipbench import programspans, serve, spec, tracereduce  # noqa: E402
from chipbench_tiny import tiny_serve_cell  # noqa: E402

SEED = 2 ** 33 + 5
A, B = "/host:CPU/0", "/host:CPU/1"

# Thread A: two requests in the window, one after it. Thread B: a compile
# that overlaps the first request in time but is nested in nothing.
# Own time: query 10-20 and 90-100, 200-210 and 240-250; validate 0-10;
# segment 20-25, 35-40, 50-60 | 60-70, 80-90 | 210-240; dispatch 25-35;
# copies 40-50 and 70-80; compile 30-130.
HAND = [
    ["dpmm.serve.query", 0, 100, {"rows": 10, "segments": 2}, A],
    ["dpmm.serve.validate", 0, 10, {}, A],
    ["dpmm.serve.segment", 20, 40, {"used": 5, "batch": 8}, A],
    ["dpmm.serve.dispatch", 25, 10, {"bytes": 100}, A],
    ["dpmm.serve.copy_back", 40, 10, {"out": "labels", "bytes": 4}, A],
    ["dpmm.serve.segment", 60, 30, {"used": 5, "batch": 8}, A],
    ["dpmm.serve.copy_back", 70, 10, {"out": "logprobs", "bytes": 40}, A],
    ["dpmm.serve.query", 200, 50, {"rows": 1, "segments": 1}, A],
    ["dpmm.serve.segment", 210, 30, {"used": 1, "batch": 8}, A],
    ["dpmm.serve.compile", 30, 100, {"kind": "q", "batch": 8}, B],
    ["dpmm.serve.query", 700, 20, {"rows": 3, "segments": 1}, A],
]
WINDOW = (0.0, 600.0)
# Device 0 busy 0-15, 28-33, 45-55, 205-245; device 1 busy 0-100;
# compilation left out 85-95.
DEVICES = {"/device:TPU:0": [["f", 0, 15], ["f", 28, 5], ["f", 45, 10],
                             ["f", 205, 40]],
           "/device:TPU:1": [["g", 0, 100]]}
EXCLUDE = [(85.0, 95.0)]


def test_self_time_is_the_span_less_its_children_on_its_thread():
    got = programspans.reduce_program(HAND, WINDOW, programspans.REQUEST_SPAN)
    ns = pytest.approx
    assert sorted(got) == ["dpmm.serve.copy_back", "dpmm.serve.dispatch",
                           "dpmm.serve.query", "dpmm.serve.segment",
                           "dpmm.serve.validate"]
    q = got["dpmm.serve.query"]
    assert q["count"] == 2
    assert q["total_s"] == ns(150e-9) and q["self_s"] == ns(40e-9)
    assert q["args"] == {"rows": 11, "segments": 3}
    seg = got["dpmm.serve.segment"]
    assert (seg["count"], seg["total_s"], seg["self_s"]) == (
        3, ns(100e-9), ns(70e-9))
    assert seg["args"] == {"used": 11, "batch": 24}
    copy = got["dpmm.serve.copy_back"]
    assert copy["self_s"] == ns(20e-9) and copy["args"] == {"bytes": 44}
    assert copy["split_s"] == {"out=labels": ns(10e-9),
                               "out=logprobs": ns(10e-9)}
    # the request's self times add up to the requests' total
    assert sum(r["self_s"] for r in got.values()) == ns(q["total_s"])


def test_without_a_root_every_span_starting_in_the_window_counts():
    got = programspans.reduce_program(HAND, WINDOW)
    compile_ = got["dpmm.serve.compile"]
    # on its own thread: nothing of thread A is its child, nor it theirs
    assert compile_["self_s"] == pytest.approx(100e-9)
    assert compile_["args"] == {"batch": 8}
    assert compile_["split_s"] == {"kind=q": pytest.approx(100e-9)}
    assert got["dpmm.serve.query"]["count"] == 2
    assert got["dpmm.serve.dispatch"]["self_s"] == pytest.approx(10e-9)
    assert programspans.reduce_program(HAND, (650.0, 800.0))[
        "dpmm.serve.query"]["args"] == {"rows": 3, "segments": 1}


def test_device_idle_goes_to_the_innermost_span_with_compilation_out():
    got = programspans.engine_idle(DEVICES, HAND, WINDOW, EXCLUDE)
    by = got["by_span_s"]
    # device 0: query 10 + 10, segment 15 + 15, dispatch 5, copies 5 + 10;
    # device 1 (idle from 100): query 20, segment 30; averaged
    assert by == {"dpmm.serve.copy_back": pytest.approx(7.5e-9),
                  "dpmm.serve.dispatch": pytest.approx(2.5e-9),
                  "dpmm.serve.query": pytest.approx(20e-9),
                  "dpmm.serve.segment": pytest.approx(30e-9),
                  "dpmm.serve.validate": pytest.approx(0.0)}
    assert got["idle_s"] == pytest.approx(60e-9)
    whole = tracereduce.reduce_events({"devices": DEVICES, "host": []},
                                      WINDOW, EXCLUDE)
    idle_s = whole["window_s"] - whole["busy_s"]
    assert idle_s == pytest.approx(510e-9)
    assert got["idle_s"] <= idle_s
    with pytest.raises(ValueError):
        programspans.engine_idle({}, HAND, WINDOW)


def test_engine_readings_by_hand_and_none_without_request_spans():
    spans = programspans.reduce_program(HAND, WINDOW,
                                        programspans.REQUEST_SPAN)
    idle = programspans.engine_idle(DEVICES, HAND, WINDOW, EXCLUDE)
    got = programspans.engine_readings(spans, idle, 2, 590e-9)
    assert got["serve_engine_ms"]["value"] == pytest.approx(75e-6)
    assert sum(got["serve_engine_ms"]["self_ms"].values()) == \
        pytest.approx(75e-6)
    assert got["serve_copy_back_ms"] == {
        "value": pytest.approx(10e-6), "bytes_per_request": 22.0,
        "by_out_ms": {"labels": pytest.approx(5e-6),
                      "logprobs": pytest.approx(5e-6)}}
    assert got["engine_idle_pct"]["value"] == pytest.approx(100 * 60 / 590)
    assert got["engine_idle_pct"]["by_span"]["dpmm.serve.segment"] == \
        pytest.approx(30e-6)
    assert got["serve_pad_efficiency"]["value"] == pytest.approx(
        100 * 11 / 24)
    assert programspans.engine_readings({}, idle, 2, 590e-9) is None
    assert programspans.engine_readings(
        programspans.reduce_program(HAND, (650.0, 660.0)), idle, 2,
        590e-9) is None
    assert programspans.engine_readings(spans, idle, 0, 590e-9) is None


def test_a_recorded_trace_keeps_the_benchmark_spans_apart():
    """``tracereduce.load_events`` reads the ``bench.*`` spans alone, as
    before the engine had spans; ``load_program`` reads the ``dpmm.*``
    ones with their arguments."""
    import jax
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            with serve.span("bench.request"):
                with jax.profiler.TraceAnnotation("dpmm.serve.query",
                                                  rows=123, segments=1):
                    with jax.profiler.TraceAnnotation(
                            "dpmm.serve.copy_back", out="labels",
                            bytes=1024):
                        pass
            with serve.span("bench.wait"):
                pass
        events = tracereduce.load_events(trace_dir)
        program = programspans.load_program(trace_dir)
    assert [name for name, _, _ in events["host"]] == ["bench.request",
                                                       "bench.wait"]
    assert all(len(e) == 3 for e in events["host"])
    assert [(e[0], e[3]) for e in program] == [
        ("dpmm.serve.query", {"rows": 123, "segments": 1}),
        ("dpmm.serve.copy_back", {"out": "labels", "bytes": 1024})]
    assert program[0][4] == program[1][4]          # one thread
    request = events["host"][0]
    assert request[1] <= program[0][1]
    assert program[0][1] + program[0][2] <= request[1] + request[2]


@pytest.fixture(scope="module")
def cell():
    return tiny_serve_cell()


@pytest.fixture(scope="module")
def prep(cell):
    return serve.prepare(cell, SEED)


def test_the_engine_marks_every_phase_of_a_request(prep):
    """On the CPU: one request span per request, the segments' ``used``
    sum to the rows sent, every copy back is at its padded size, the
    answers are the untraced engine's, and no host time goes unnamed
    beyond the request's own bookkeeping."""
    import jax
    engine, pool = prep["engine"], prep["pool"]
    sizes = [1, 300, 2048, 9000, 16000]
    plain = [engine.query(pool[:n]) for n in sizes]
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            got = [engine.query(pool[:n]) for n in sizes]
        program = programspans.load_program(trace_dir)
    for a, b in zip(plain, got):
        assert np.array_equal(a.labels, b.labels)
        assert np.array_equal(a.logprobs, b.logprobs)
        assert np.array_equal(a.log_predictive, b.log_predictive)
    window = (min(e[1] for e in program), max(e[1] + e[2] for e in program))
    spans = programspans.reduce_program(program, window)
    assert "dpmm.serve.compile" not in spans       # warmed at build
    routes = [engine.plan_route(n) for n in sizes]
    queries = [e for e in program if e[0] == programspans.REQUEST_SPAN]
    assert [q[3] for q in queries] == [
        {"rows": n, "segments": len(r)} for n, r in zip(sizes, routes)]
    batches = [b for r in routes for _, _, b in r]
    assert spans["dpmm.serve.segment"]["args"] == {
        "used": sum(sizes), "batch": sum(batches)}
    for name in ("dpmm.serve.pad", "dpmm.serve.dispatch"):
        assert spans[name]["count"] == len(batches)
    assert spans["dpmm.serve.dispatch"]["args"]["bytes"] == \
        sum(batches) * engine.d * 4
    copies = [e for e in program if e[0] == "dpmm.serve.copy_back"]
    assert sorted(c[3]["out"] for c in copies) == sorted(
        ["labels", "logprobs", "log_predictive"] * len(batches))
    per_row = {"labels": 4, "log_predictive": 4, "logprobs": 4 * engine.k_max}
    assert sum(c[3]["bytes"] for c in copies) == sum(
        b * sum(per_row.values()) for b in batches)
    for c in copies:
        assert c[3]["bytes"] % per_row[c[3]["out"]] == 0
    assert spans["dpmm.serve.assemble"]["count"] == len(sizes)
    query = spans[programspans.REQUEST_SPAN]
    assert sum(r["self_s"] for r in spans.values()) == pytest.approx(
        query["total_s"])
    assert query["self_s"] < 0.5 * query["total_s"]


@contextlib.contextmanager
def one_device_op_per_dispatch():
    """The CPU trace has no TPU plane: put one operation on a stand-in
    device plane for each step dispatch, half as long as it."""
    orig = tracereduce.load_events

    def load(trace_dir):
        events = orig(trace_dir)
        ops = [["fusion", s, d / 2]
               for name, s, d, *_ in programspans.load_program(trace_dir)
               if name == "dpmm.serve.dispatch"]
        return dict(events, devices={"/device:TPU:0": ops,
                                     "/device:TPU:1": []})
    with mock.patch.object(tracereduce, "load_events", load):
        yield


def test_the_spans_tool_reads_a_serving_window(cell, prep):
    import spans as tool
    cell = dict(cell, mix=dict(cell["mix"], trace_seconds=1.0))
    with one_device_op_per_dispatch():
        got = tool.traced(cell, prep)
    sched = serve.schedule(cell["mix"], 1.0, prep["traffic_seed"])
    routes = [prep["engine"].plan_route(int(n)) for n in sched["rows"]]
    batch = sum(b for r in routes for _, _, b in r)
    read = got["readings"]
    assert got["latency"]["requests"] == len(sched["rows"])
    assert read["serve_pad_efficiency"]["value"] == pytest.approx(
        100.0 * sched["rows"].sum() / batch)
    assert read["serve_copy_back_ms"]["bytes_per_request"] == pytest.approx(
        batch * (8 + 4 * prep["engine"].k_max) / len(sched["rows"]))
    engine_ms = read["serve_engine_ms"]
    assert sum(engine_ms["self_ms"].values()) == pytest.approx(
        engine_ms["value"])
    # the request span covers the query the host clock times around it
    assert 0.5 * got["latency"]["engine_ms"] < engine_ms["value"] <= \
        1.01 * got["latency"]["engine_ms"]
    assert 0 < read["engine_idle_pct"]["value"] <= \
        got["device_idle_pct.serve"] <= 100
    assert sum(read["engine_idle_pct"]["by_span"].values()) > 0
    assert got["outside_requests"] == {}


ENGINE_METRICS = ["serve_engine_ms", "serve_copy_back_ms", "engine_idle_pct",
                  "serve_pad_efficiency"]


def _hand_context(program=HAND):
    """``serve.trace_context`` over the hand-made spans and device
    operations: the serving window is 0-600 ns on a clock that starts at
    the loop's wall start, compilation 85-95 ns, two requests answered
    and a third not, and a chip the engine never touched."""
    events = {"devices": dict(DEVICES, **{"/device:TPU:2": []}),
              "host": [["bench.serve_window", 0.0, 600.0],
                       ["bench.wait", 100.0, 100.0]]}
    loop = {"wall_start": 0.0,
            "latency_s": np.array([1e-3, 2e-3, np.nan])}
    sched = {"rows": np.array([10, 1, 3])}
    served = {"d": 4, "slots": [0, 1, 2], "k_max": 8}
    cell = {"config": {"family": "gaussian"}}
    return serve.trace_context(cell, events, program, loop, sched, served,
                               "TPU v5 lite", [(85e-9, 95e-9)])


def test_the_cells_context_carries_the_programs_spans_and_the_engine():
    ctx = _hand_context()
    trace = tracereduce.reduce_events({"devices": DEVICES, "host": []},
                                      WINDOW, EXCLUDE)
    assert ctx["trace"]["busy_s"] == trace["busy_s"]
    assert ctx["trace"]["window_s"] == trace["window_s"]
    assert ctx["chips"] == 2
    assert ctx["query_work"] == spec.load_module(
        "counts", "gaussian").query_work(11, 4, 3, 8)
    assert ctx["peaks"] == spec.peaks("TPU v5 lite")
    assert ctx["program"] == programspans.reduce_program(HAND, WINDOW)
    assert "dpmm.serve.compile" in ctx["program"]
    assert ctx["engine"] == programspans.engine_readings(
        programspans.reduce_program(HAND, WINDOW, programspans.REQUEST_SPAN),
        programspans.engine_idle(DEVICES, HAND, WINDOW, EXCLUDE), 2,
        trace["window_s"])


@pytest.mark.parametrize("name", ENGINE_METRICS)
def test_each_engine_reader_reads_its_number_or_nothing(name):
    reader = spec.load_module("metrics", name).read
    ctx = _hand_context()
    assert reader(ctx) == ctx["engine"][name]
    assert reader(ctx)["value"] > 0
    no_request = [e for e in HAND if e[0] != programspans.REQUEST_SPAN]
    bare = _hand_context(no_request)
    assert bare["engine"] is None and bare["program"]
    assert reader(bare) is None
    assert reader({}) is None


def test_a_traced_serving_run_reports_the_engines_readings(cell, prep):
    """On the CPU, with a stand-in device plane and the v5e's peaks: the
    traced run's line carries every per-layer metric of the cell, the
    engine's four among them, and each reads as the context it was
    given."""
    import jax
    cell = dict(cell, mix=dict(cell["mix"], trace_seconds=1.0))
    peaks = spec.peaks("TPU v5 lite")
    seen = {}
    orig = serve.trace_context

    def keep(*args):
        seen["ctx"] = orig(*args)
        return seen["ctx"]
    with one_device_op_per_dispatch(), \
            mock.patch.object(spec, "peaks", lambda kind: peaks), \
            mock.patch.object(serve, "trace_context", keep):
        line = serve.run_cell(cell, SEED, 1.0, True, jax.devices(), 0.0,
                              prep=prep, say=lambda m: None)
    assert line["correct"], line["checks"]
    metrics = line["metrics"]
    assert set(metrics) == {m["name"] for m in cell["per_layer"]}
    assert set(ENGINE_METRICS) <= set(metrics)
    units = {m["name"]: m["unit"] for m in cell["per_layer"]}
    for name in ENGINE_METRICS:
        assert metrics[name] == dict(seen["ctx"]["engine"][name],
                                     unit=units[name])
    sched = serve.schedule(cell["mix"], 1.0, prep["traffic_seed"])
    routes = [prep["engine"].plan_route(int(n)) for n in sched["rows"]]
    batch = sum(b for r in routes for _, _, b in r)
    assert metrics["serve_pad_efficiency"]["value"] == pytest.approx(
        100.0 * sched["rows"].sum() / batch)


def test_a_fit_trace_context_carries_the_programs_spans():
    """``run.trace_context`` reduces the program's spans over the fit's
    window, from the first chunk's dispatch to the window's end."""
    import jax
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            with run.span("bench.window_fit"):
                with run.span("bench.chunk_dispatch"):
                    with jax.profiler.TraceAnnotation("dpmm.fit.chunk",
                                                      iters=5):
                        pass
        with mock.patch.object(tracereduce, "load_events",
                               _with_a_device_op(tracereduce.load_events)):
            ctx = run.trace_context(
                {"config": {"family": "gaussian",
                            "data": {"n": 100, "d": 2}}, "chips": 1},
                trace_dir, {"start": 0.0, "compile_spans": []}, 5, 3.0,
                "TPU v5 lite")
    assert ctx["program"]["dpmm.fit.chunk"]["count"] == 1
    assert ctx["program"]["dpmm.fit.chunk"]["args"] == {"iters": 5}
    assert ctx["work"] == spec.load_module("counts", "gaussian").work(
        100, 2, 3.0)


def _with_a_device_op(load):
    """``load`` with one operation on a stand-in device plane, covering
    the benchmark's first host span."""
    def loaded(trace_dir):
        events = load(trace_dir)
        _, s, d = events["host"][0]
        return dict(events, devices={"/device:TPU:0": [["fusion", s, d]]})
    return loaded
