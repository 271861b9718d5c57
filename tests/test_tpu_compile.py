"""The main-path Pallas kernels compile for a TPU v5e (Mosaic), at the widths
``chip_smoke.py`` runs: the sweep megakernels at the top of the paper's
Gaussian grid (d=32) and at the bag-of-words width (d'=512) with k_max=64,
and the serving kernels at the ladder's largest step (8192 rows).

Interpret mode accepts layouts Mosaic refuses (rank-1 sub-128 blocks,
unaligned block dims, in-kernel gathers, unsigned-to-float casts), so the
CPU parity suites alone cannot show a kernel will lower. These tests
compile for a *described* chip — no TPU needed — and require the Mosaic
custom call in the compiled program. The topology is described inside a
module-scoped fixture (the TPU library may be loaded by one process at a
time, so nothing here touches it at import).
"""
import contextlib
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import assign, loglik, suffstats, sweep

N_SERVE = 8192          # the serving ladder's largest AOT step
N_SWEEP = 8192          # 8 STATS_BLOCKs of points
K = 64
F32, I32, U32 = jnp.float32, jnp.int32, jnp.uint32


@contextlib.contextmanager
def _no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape():
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct placed on one chip of
    a described v5e:2x2."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp, _no_persistent_cache():
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:        # a jax[cpu] install has no libtpu
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        chip = SingleDeviceSharding(topo.devices[0])
        yield lambda dims, dtype=F32: jax.ShapeDtypeStruct(dims, dtype,
                                                           sharding=chip)


def _assert_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sweep_gauss_compiles(shape):
    d = 32
    _assert_mosaic(
        sweep.sweep_gauss,
        shape((N_SWEEP, d)), shape((K, d)), shape((K, d, d)), shape((K,)),
        shape((K,)), shape((K,), I32), shape((K, 2, d)),
        shape((K, 2, d, d)), shape((K, 2)), shape((K, 2)),
        shape((N_SWEEP,)), shape((N_SWEEP,), U32), shape((2,), U32),
        shape((2,), U32), shape((K,), U32))


def test_sweep_linear_compiles(shape):
    dp = 512
    _assert_mosaic(
        sweep.sweep_linear,
        shape((N_SWEEP, dp)), shape((K, dp)), shape((K,)), shape((K,)),
        shape((K,), I32), shape((K, 2, dp)), shape((K, 2)), shape((K, 2)),
        shape((N_SWEEP,)), shape((N_SWEEP,), U32), shape((2,), U32),
        shape((2,), U32), shape((K,), U32))


def test_assign_gauss_compiles(shape):
    d = 32
    _assert_mosaic(
        assign.assign_gauss,
        shape((N_SERVE, d)), shape((K, d)), shape((K, d, d)), shape((K,)),
        shape((K,)), shape((K,), I32), shape((N_SERVE,), U32),
        shape((2,), U32), shape((K,), U32))


def test_assign_linear_compiles(shape):
    dp = 512
    _assert_mosaic(
        assign.assign_linear,
        shape((N_SERVE, dp)), shape((K, dp)), shape((K,)), shape((K,)),
        shape((K,), I32), shape((N_SERVE,), U32), shape((2,), U32),
        shape((K,), U32))


def test_loglik_compiles(shape):
    d = 32
    _assert_mosaic(loglik.loglik, shape((N_SERVE, d)), shape((K, d)),
                   shape((K, d, d)), shape((K,)))


@pytest.mark.parametrize("family", ["gaussian", "linear"])
def test_sub_assign_compiles(shape, family):
    """Step (f) alone: what a sweep that falls back to the reference body
    runs under use_pallas."""
    if family == "gaussian":
        d = 32
        _assert_mosaic(
            assign.sub_assign_gauss,
            shape((N_SWEEP, d)), shape((K, 2, d)), shape((K, 2, d, d)),
            shape((K, 2)), shape((K, 2)), shape((N_SWEEP,), I32),
            shape((N_SWEEP,), U32), shape((2,), U32))
    else:
        dp = 512
        _assert_mosaic(
            assign.sub_assign_linear,
            shape((N_SWEEP, dp)), shape((K, 2, dp)), shape((K, 2)),
            shape((K, 2)), shape((N_SWEEP,), I32), shape((N_SWEEP,), U32),
            shape((2,), U32))


@pytest.mark.parametrize("kernel", ["suffstats_labels", "moments_labels",
                                    "suffstats"])
def test_label_stats_compile(shape, kernel):
    """The split/merge consistency fold's per-block stat kernels, and the
    dense-responsibility fold (``ops.suffstats_pallas``)."""
    d = 32
    if kernel == "suffstats":
        _assert_mosaic(suffstats.suffstats, shape((N_SWEEP, d)),
                       shape((N_SWEEP, K)))
        return
    labels = (shape((N_SWEEP,), I32), shape((N_SWEEP,), I32),
              shape((N_SWEEP,)))
    fn = functools.partial(getattr(suffstats, kernel), k=K)
    _assert_mosaic(fn, shape((N_SWEEP, d)), *labels)
