"""Distribution tests (paper §4.3 / claim C3): chains are bitwise identical
across mesh sizes, and the ONLY cross-shard traffic is the psum of
sufficient statistics — never the O(N d) point data."""
import os
import re

import numpy as np
import pytest

# 4 virtual CPU devices for every test in this file (set before jax import
# via conftest would leak into other files; spawn check handled by pytest
# forking? No — set here only if jax is not yet initialized).
import jax

if jax.device_count() == 1:
    pytest.skip("needs >1 device (tests/conftest.py sets 4 virtual CPU "
                "devices when run via pytest)", allow_module_level=True)

import functools

import jax.numpy as jnp

from repro.configs import DPMMConfig
from repro.core import niw
from repro.core.distributed import make_data_mesh
from repro.core.sampler import DPMM
from repro.data.synthetic import generate_gmm


@pytest.fixture(scope="module")
def data():
    return generate_gmm(4096, 4, 5, seed=0, sep=10.0)


def test_chain_identical_across_meshes(data):
    """fold_in(global index) PRNG => 1-dev and N-dev runs match bitwise."""
    x, gt = data
    cfg = DPMMConfig(alpha=10.0, iters=30, k_max=16, burnout=5)
    r1 = DPMM(cfg, mesh=make_data_mesh(1)).fit(x)
    rn = DPMM(cfg, mesh=make_data_mesh(jax.device_count())).fit(x)
    assert r1.k == rn.k
    assert np.array_equal(r1.labels, rn.labels)


def test_only_suffstats_cross_shards(data):
    """Structural HLO check: every collective operand is O(K*T) (suff-stats
    / scalars), never O(N_local * d) (the sharded points)."""
    x, _ = data
    cfg = DPMMConfig(alpha=10.0, iters=5, k_max=16, burnout=2)
    mesh = make_data_mesh(jax.device_count())
    model = DPMM(cfg, mesh=mesh)

    # reproduce the fit()'s compiled step to inspect its HLO
    from repro.core.sampler import _init_local, dpmm_step
    from repro.core.distributed import data_axes_of, shard_points
    from repro.core.family import state_partition_specs
    from jax.sharding import PartitionSpec as P

    axes = data_axes_of(mesh)
    prior = model.family.build_prior(cfg, x)
    xs, valid = shard_points(mesh, np.asarray(x, np.float32), False)
    kwargs = dict(prior=prior, family=model.family, cfg=cfg, axes=axes,
                  k_max=cfg.k_max)
    shard_spec = P(axes)
    rep = P()
    state_specs = state_partition_specs(model.family, shard_spec)
    init = jax.jit(jax.shard_map(
        functools.partial(_init_local, **kwargs),
        mesh=mesh, in_specs=(rep, shard_spec, shard_spec),
        out_specs=state_specs, check_vma=False))
    model_state, point_state = init(jax.random.key(0), xs, valid)
    step = jax.jit(jax.shard_map(
        functools.partial(dpmm_step, **kwargs), mesh=mesh,
        in_specs=(*state_specs, shard_spec),
        out_specs=state_specs, check_vma=False))
    hlo = step.lower(model_state, point_state, xs).compile().as_text()

    n_local = x.shape[0] // jax.device_count()
    d = x.shape[1]
    data_bytes = n_local * d * 4
    # every collective's result must be far smaller than the local shard
    pat = re.compile(r"=\s*((?:\([^)]*\))|\S+)\s+(all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)\(")
    from repro.roofline.hlo_costs import _shape_bytes
    found = 0
    for line in hlo.splitlines():
        m = pat.search(line)
        if not m:
            continue
        found += 1
        nbytes = _shape_bytes(m.group(1))
        assert nbytes < data_bytes / 4, (
            f"collective moves {nbytes}B >= shard/4 "
            f"({data_bytes}B): {line[:160]}")
    assert found > 0, "expected at least one suff-stat psum"


def test_weak_scaling_suffstat_volume(data):
    """Communication volume per sweep is independent of N (paper: only
    sufficient statistics and parameters cross the wire)."""
    x, _ = data
    cfg = DPMMConfig(alpha=10.0, iters=2, k_max=16, burnout=1)
    mesh = make_data_mesh(jax.device_count())
    from repro.roofline.hlo_costs import analyze_hlo

    def coll_bytes(n_points):
        model = DPMM(cfg, mesh=mesh)
        r = model.fit(x[:n_points], iters=1)
        return r

    # indirect but effective: K*T floats for gaussian d=4, K_max=16:
    # stats ~ 16*(1+4+16)*4B*2(sub) ~ 2.7KB/psum — assert via the HLO of
    # the structural test above; here we just confirm fit works at 2 sizes
    assert coll_bytes(1024).k >= 1
    assert coll_bytes(4096).k >= 1


def test_feature_sharded_poisson_identical():
    """Poisson feature-sharding (rates are feature-independent too)."""
    from jax.sharding import Mesh
    from repro.data.synthetic import generate_pmm

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    x, gt = generate_pmm(1024, 16, 4, seed=2)
    cfg = DPMMConfig(component="poisson", alpha=10.0, iters=20,
                     k_max=16, burnout=5)
    r_plain = DPMM(cfg).fit(x)
    mesh22 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                  ("data", "model"))
    cfg_fs = DPMMConfig(component="poisson", alpha=10.0, iters=20,
                        k_max=16, burnout=5, shard_features=True)
    r_fs = DPMM(cfg_fs, mesh=mesh22).fit(x)
    assert np.array_equal(r_plain.labels, r_fs.labels)


def test_feature_sharded_multinomial_identical():
    """High-d multinomial mode (DESIGN §10): x's feature dim sharded over
    'model' — local x @ log(theta) partials + psum. Chain must be bitwise
    identical to the unsharded run (the paper's d=20,000 regime)."""
    from jax.sharding import Mesh
    from repro.data.synthetic import generate_mnmm

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    x, gt = generate_mnmm(1024, 32, 5, seed=1)
    cfg = DPMMConfig(component="multinomial", alpha=10.0, iters=25,
                     k_max=16, burnout=5)
    r_plain = DPMM(cfg).fit(x)
    mesh22 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                  ("data", "model"))
    cfg_fs = DPMMConfig(component="multinomial", alpha=10.0, iters=25,
                        k_max=16, burnout=5, shard_features=True)
    r_fs = DPMM(cfg_fs, mesh=mesh22).fit(x)
    assert r_plain.k == r_fs.k
    assert np.array_equal(r_plain.labels, r_fs.labels)


def test_feature_sharded_diag_gaussian_identical():
    """diag_gaussian is feature-separable (per-feature NIG), so it gets the
    high-d sharded path the full-covariance Gaussian can't have — the
    registry's feature_shardable contract in action."""
    from jax.sharding import Mesh

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    x, gt = generate_gmm(1024, 16, 4, seed=3, sep=8.0)
    cfg = DPMMConfig(component="diag_gaussian", alpha=10.0, iters=25,
                     k_max=16, burnout=5)
    r_plain = DPMM(cfg).fit(x)
    mesh22 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                  ("data", "model"))
    cfg_fs = DPMMConfig(component="diag_gaussian", alpha=10.0, iters=25,
                        k_max=16, burnout=5, shard_features=True)
    r_fs = DPMM(cfg_fs, mesh=mesh22).fit(x)
    assert r_plain.k == r_fs.k
    assert np.array_equal(r_plain.labels, r_fs.labels)


def test_gaussian_shard_features_falls_back_to_replicated():
    """shard_features with a non-separable family must not silently shard:
    fit() keeps the replicated-feature path and still works."""
    from jax.sharding import Mesh

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    x, gt = generate_gmm(512, 4, 3, seed=4, sep=10.0)
    mesh22 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                  ("data", "model"))
    cfg = DPMMConfig(alpha=10.0, iters=10, k_max=8, burnout=3,
                     shard_features=True)
    r = DPMM(cfg, mesh=mesh22).fit(x)
    assert r.k >= 1
