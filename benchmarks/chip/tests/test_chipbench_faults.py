"""A whole run, with the chip check skipped, at a size the CPU holds:
sound, it is correct; with the timed path broken underneath, or with the
bfloat16 control in the program's place, ``correct`` comes out false."""
import contextlib
import sys
from pathlib import Path
from unittest import mock

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from chipbench import check, spec  # noqa: E402
from chipbench_tiny import tiny_cell  # noqa: E402

SEED = 2 ** 31 + 11          # past 32 signed bits, as the driver's are


@pytest.fixture(scope="module",
                params=["mnmm_n1m_d128_k32", "gauss_n1m_d32_k16"])
def burned(request):
    import jax
    cell = tiny_cell(request.param)
    return cell, run.prepare(cell, SEED), jax.devices()


def _run(burned, patch=None):
    cell, prep, devices = burned
    return run.run_cell(cell, SEED, 0.1, False, devices,
                        window_patch=patch, prep=prep, say=lambda m: None)


@contextlib.contextmanager
def state_unchanged():
    import repro.core.sampler as sampler
    with mock.patch.object(sampler, "dpmm_step",
                           lambda model, point, x, **kw: (model, point)):
        yield


@contextlib.contextmanager
def half_the_batch():
    """Every fold sees the first half of the points only."""
    import jax.numpy as jnp
    import repro.core.gibbs as gibbs
    orig = gibbs.sweep_tile

    def sweep_tile(model, x, point, *args, **kwargs):
        n = point.valid.shape[0]
        half = point._replace(valid=point.valid
                              * (jnp.arange(n) < n // 2).astype(
                                  point.valid.dtype))
        return orig(model, x, half, *args, **kwargs)
    with mock.patch.object(gibbs, "sweep_tile", sweep_tile):
        yield


@contextlib.contextmanager
def label_altered():
    """The sweep hands 64 points to another live cluster than it drew."""
    import jax.numpy as jnp
    import repro.core.gibbs as gibbs
    orig = gibbs.sweep_tile

    def sweep_tile(model, x, point, *args, **kwargs):
        point, acc = orig(model, x, point, *args, **kwargs)
        live = jnp.argsort(jnp.logical_not(model.active))
        lab = point.labels
        other = jnp.where(lab == live[0], live[1], live[0])
        lab = jnp.where(jnp.arange(lab.shape[0]) < 64, other, lab)
        return point._replace(labels=lab.astype(point.labels.dtype)), acc
    with mock.patch.object(gibbs, "sweep_tile", sweep_tile):
        yield


def test_a_sound_run_is_correct(burned):
    line = _run(burned)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 20 and line["failed"] == 0
    assert set(line["metrics"]) == {"iter_ms", "setup_s"}
    assert list(line)[-2:] == ["checks", "_rows"]


@pytest.mark.parametrize("fault,caught_by", [
    (state_unchanged, "iters_short"),
    (half_the_batch, "stats_gap"),
    (label_altered, "label_gap"),
])
def test_a_broken_timed_path_is_not_correct(burned, fault, caught_by):
    line = _run(burned, fault())
    assert not line["correct"]
    failed = {name for name, _, _, ok in line["_rows"] if not ok}
    assert caught_by in failed, line["checks"]


def test_the_bfloat16_control_is_not_correct(burned):
    """The control, the reference in bfloat16 in the program's place, on
    the outputs of a sound window, fails the statistics' limits."""
    import numpy as np
    cell, prep, _ = burned
    iters = 20
    win = run.measure(prep, iters)
    ref = spec.load_module("reference", cell["config"]["family"])
    out = check.program_outputs(win["result"].state, win["point"],
                                iters, ref)
    it0 = int(np.asarray(prep["burned"].state.it))
    limits = cell["config"]["limits"][run.FIT_KIND]
    sound = check.verdict(check.evaluate(ref, prep["x"], out,
                                         cell["config"], iters, it0), limits)
    control = check.verdict(check.evaluate(
        ref, prep["x"], check.control_outputs(ref, prep["x"], out),
        cell["config"], iters, it0), limits)
    assert all(ok for *_, ok in sound)
    failed = {name for name, _, _, ok in control if not ok}
    assert {"stats_gap", "substats_gap"} <= failed
