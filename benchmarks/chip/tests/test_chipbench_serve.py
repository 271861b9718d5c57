"""The serving cell's run at a size the CPU holds, with the chip check
skipped: sound, it is correct; with an answer altered where the engine
produces it, or with the bfloat16 control answering, it is not. And the
traffic: every seed offers the same work in an order of its own."""
import contextlib
import sys
import time
from pathlib import Path
from unittest import mock

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import serve  # noqa: E402
from chipbench_tiny import tiny_serve_cell  # noqa: E402

SEED = 2 ** 33 + 3           # past 32 signed bits, as the driver's are
SECONDS = 0.5


@contextlib.contextmanager
def fresh_steps():
    """A step table of its own, so that a broken step never reaches
    another test's engine (the program shares compiled steps by shape)."""
    import repro.serve.dpmm as dpmm
    with mock.patch.object(dpmm, "_TABLE", dpmm._StepTable()):
        yield


@contextlib.contextmanager
def label_altered():
    """The query step hands every row to the next served slot."""
    import jax.numpy as jnp
    import repro.serve.dpmm as dpmm
    orig = dpmm._query_fn

    def query_fn(family, k_max, use_pallas):
        step = orig(family, k_max, use_pallas)

        def broken(x, params, logw, active, slots):
            out = step(x, params, logw, active, slots)
            nxt = jnp.roll(slots, 1)
            pos = jnp.argmax(out["labels"][:, None] == slots[None, :], 1)
            return dict(out, labels=jnp.take(nxt, pos).astype(jnp.int32))
        return broken
    with fresh_steps(), mock.patch.object(dpmm, "_query_fn", query_fn):
        yield


def _run(cell, prep):
    import jax
    return serve.run_cell(cell, SEED, SECONDS, False, jax.devices(),
                          time.time(), prep=prep, say=lambda m: None)


@pytest.fixture(scope="module")
def cell():
    return tiny_serve_cell()


def test_a_sound_serving_run_is_correct(cell):
    with fresh_steps():
        line = _run(cell, serve.prepare(cell, SEED))
    assert line["correct"], line["checks"]
    assert line["attempted"] == 20 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p95_ms", "setup_s"}
    assert list(line)[-2:] == ["checks", "_rows"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(cell):
    with label_altered():
        line = _run(cell, serve.prepare(cell, SEED))
    assert not line["correct"]
    failed = {name for name, _, _, ok in line["_rows"] if not ok}
    assert "answer_gap" in failed, line["checks"]


def test_the_bfloat16_control_is_not_correct(cell):
    import jax
    import control
    got = control.serve_readings(cell, SEED, SECONDS, jax.devices())
    limit = cell["config"]["limits"][serve.KIND]["answer_gap"]
    assert got["program"]["answer_gap"] <= limit
    assert got["control"]["answer_gap"] > limit


@pytest.mark.parametrize("seconds", [0.5, 20.0])
def test_every_seed_offers_the_same_work_in_its_own_order(cell, seconds):
    mix = cell["mix"]
    a = serve.schedule(mix, seconds, 1)
    b = serve.schedule(mix, seconds, 2 ** 40 + 1)
    n = round(mix["rate_rps"] * seconds)
    assert len(a["rows"]) == len(b["rows"]) == n
    assert sorted(a["rows"]) == sorted(b["rows"])
    assert np.array_equal(np.sort(a["gaps"]), np.sort(b["gaps"]))
    assert np.allclose(a["due"][1:], np.cumsum(a["gaps"])[:-1])
    assert a["rows"].min() >= mix["rows_min"]
    assert a["rows"].max() <= mix["rows_max"]
    assert (a["offsets"] + a["rows"] <= mix["pool_rows"]).all()
    assert int(np.argmax(a["rows"])) in a["check"]
    if n > 100:
        assert a["rows"].tolist() != b["rows"].tolist()
        # the offered rate is the mix's, to a few per cent
        assert abs(a["due"][-1] / seconds - 1.0) < 0.05
