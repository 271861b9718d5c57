"""Interpret-mode parity for the fused sweep hot path (kernels/assign.py,
kernels/suffstats.py, kernels/prng.py) against the jnp reference path:

 - fused assignment labels IDENTICAL to the reference argmax, and fused
   sub-assignment labels identical to the chunked own-cluster gather, for
   every registered family, on both MXU-aligned and ragged (N, K) shapes;
 - label-indexed suff-stats (segment-sum / one-hot reference AND Pallas
   kernels) allclose to the dense stats_from_points oracle;
 - feature-sharded assignment/sub-assignment bitwise equal to replicated;
 - the structural guarantee behind the perf claim: the reference sweep's
   jaxpr contains NO (N, K, 2) intermediate — step (f) evaluates only each
   point's own cluster, on every path.
"""
import functools

import numpy as np
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import pytest

from repro.configs import DPMMConfig
from repro.core import gibbs
from repro.core.family import available_families, get_family
from repro.kernels import prng

ALL = available_families()
SHARDABLE = [n for n in ALL if get_family(n).feature_shardable]

# (N, K, d): one MXU-aligned problem, one ragged one that exercises the
# kernels' padding of both the point and cluster axes
SHAPES = [(128, 8, 4), (130, 7, 5)]


def _data(name, n, d, rng):
    if name in ("gaussian", "diag_gaussian"):
        return rng.normal(2.0, 1.5, size=(n, d)).astype(np.float32)
    if name == "poisson":
        return rng.poisson(4.0, size=(n, d)).astype(np.float32)
    return rng.multinomial(30, np.ones(d) / d, size=n).astype(np.float32)


def _setup(name, n, k, d, seed=0):
    """Params/weights for k slots with the last slot inactive (tests the
    kernels' active-mask handling next to real clusters)."""
    fam = get_family(name)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(_data(name, n, d, rng))
    prior = fam.build_prior(DPMMConfig(component=name), x)
    labels0 = jnp.asarray(rng.integers(0, max(k - 1, 1), n), jnp.int32)
    bits0 = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
    valid = jnp.ones((n,), bool)
    substats = fam.stats_from_labels(x, valid, labels0, bits0, k)
    stats = jax.tree.map(lambda a: jnp.sum(a, axis=1), substats)
    params = fam.sample_posterior(jax.random.key(seed), prior, stats)
    subparams = fam.sample_posterior(jax.random.key(seed + 1), prior,
                                     substats)
    active = jnp.arange(k) < (k - 1 if k > 1 else 1)
    logw = jnp.where(active, jnp.asarray(
        rng.normal(-1.5, 0.3, k), jnp.float32), gibbs.NEG_INF)
    sublogw = jnp.asarray(rng.normal(-0.7, 0.1, (k, 2)), jnp.float32)
    gidx = jnp.arange(n, dtype=jnp.uint32)
    key_data = prng.key_words(jax.random.key(seed + 2))
    return fam, x, valid, params, subparams, active, logw, sublogw, \
        gidx, key_data


# ---------------------------------------------------------------------------
# threefry / gumbel
# ---------------------------------------------------------------------------
def test_threefry_matches_jax_prng():
    """Our counter-based Threefry-2x32 is bit-for-bit JAX's own."""
    try:
        from jax._src.prng import threefry_2x32
    except ImportError:
        pytest.skip("jax internal threefry not importable")
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.integers(0, 2**32, 2), jnp.uint32)
    c = jnp.asarray(rng.integers(0, 2**32, (2, 64)), jnp.uint32)
    y0, y1 = prng.threefry2x32(k[0], k[1], c[0], c[1])
    want = np.asarray(threefry_2x32(k, jnp.concatenate([c[0], c[1]])))
    assert np.array_equal(np.concatenate([y0, y1]), want)


def test_gumbel_moments():
    g = prng.gumbel(prng.key_words(jax.random.key(0)),
                    jnp.arange(200_000, dtype=jnp.uint32)[:, None],
                    jnp.arange(2, dtype=jnp.uint32)[None, :])
    assert bool(jnp.isfinite(g).all())
    assert abs(float(g.mean()) - 0.5772) < 0.01      # Euler-Mascheroni
    assert abs(float(g.var()) - 1.6449) < 0.02       # pi^2 / 6


# ---------------------------------------------------------------------------
# step (e): fused assignment vs reference argmax
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,k,d", SHAPES)
@pytest.mark.parametrize("name", ALL)
def test_assign_fused_labels_identical(name, n, k, d):
    fam, x, _, params, _, active, logw, _, gidx, key_data = _setup(
        name, n, k, d)
    fused = fam._assign_fused(x, params, logw, active, gidx, key_data)
    assert fused is not None, "fused path unexpectedly guarded out"
    ref = fam.assign(x, params, logw, active, gidx, key_data,
                     use_pallas=False)
    assert fused.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))
    # labels only ever point at active clusters
    assert bool(active[np.asarray(ref)].all())


# ---------------------------------------------------------------------------
# step (f): fused own-cluster sub-assignment vs chunked-gather reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,k,d", SHAPES)
@pytest.mark.parametrize("name", ALL)
def test_sub_assign_fused_labels_identical(name, n, k, d):
    fam, x, _, params, subparams, active, logw, sublogw, gidx, key_data = \
        _setup(name, n, k, d)
    labels = fam.assign(x, params, logw, active, gidx, key_data)
    fused = fam._sub_assign_fused(x, subparams, sublogw, labels, gidx,
                                  key_data)
    assert fused is not None, "fused path unexpectedly guarded out"
    ref = fam.sub_assign(x, subparams, sublogw, labels, gidx, key_data,
                         use_pallas=False, chunk=64)   # force >1 map step
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))
    assert set(np.unique(np.asarray(ref))) <= {0, 1}


def test_sub_assign_reference_chunking_invariant():
    """The chunk size is a pure performance knob."""
    fam, x, _, params, subparams, active, logw, sublogw, gidx, key_data = \
        _setup("gaussian", 130, 7, 5)
    labels = fam.assign(x, params, logw, active, gidx, key_data)
    outs = [np.asarray(fam.sub_assign(x, subparams, sublogw, labels, gidx,
                                      key_data, chunk=c))
            for c in (1000, 64, 13)]
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


# ---------------------------------------------------------------------------
# label-indexed suff-stats: reference AND Pallas vs the dense oracle
# ---------------------------------------------------------------------------
def _dense_oracle(fam, x, valid, labels, sublabels, k):
    """The pre-fusion formulation: dense resp x subresp matmuls."""
    resp = jax.nn.one_hot(labels, k, dtype=x.dtype) * valid[:, None]
    sub = jax.nn.one_hot(sublabels, 2, dtype=x.dtype)
    subresp = resp[:, :, None] * sub[:, None, :]
    return fam.stats_from_points(x, subresp)


@pytest.mark.parametrize("n,k,d", SHAPES)
@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["reference", "pallas"])
@pytest.mark.parametrize("name", ALL)
def test_stats_from_labels_matches_dense_oracle(name, use_pallas, n, k, d):
    fam = get_family(name)
    rng = np.random.default_rng(n + k + d)
    x = jnp.asarray(_data(name, n, d, rng))
    labels = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    sublabels = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.9)        # exercise padding mask
    got = fam.stats_from_labels(x, valid, labels, sublabels, k,
                                use_pallas=use_pallas)
    want = _dense_oracle(fam, x, valid.astype(x.dtype), labels, sublabels, k)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-4, atol=1e-3),
        got, want)
    # cluster stats are the exact fold over the sub axis
    folded = jax.tree.map(lambda a: jnp.sum(a, axis=1), got)
    resp = jax.nn.one_hot(labels, k, dtype=x.dtype) \
        * valid.astype(x.dtype)[:, None]
    full = fam.stats_from_points(x, resp)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-4, atol=1e-3),
        folded, full)


# ---------------------------------------------------------------------------
# feature-sharded parity (the high-d regime, DESIGN §10)
# ---------------------------------------------------------------------------
def _feat_mesh():
    from jax.sharding import Mesh
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (tests/conftest.py sets 4)")
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))


@pytest.mark.parametrize("name", SHARDABLE)
def test_assign_feature_sharded_identical(name):
    from jax.sharding import PartitionSpec as P
    mesh = _feat_mesh()
    n, k, d = 128, 8, 8
    fam, x, _, params, subparams, active, logw, sublogw, _, key_data = \
        _setup(name, n, k, d)
    gidx = jnp.arange(n, dtype=jnp.uint32)
    plain = fam.assign(x, params, logw, active, gidx, key_data)
    sub_plain = fam.sub_assign(x, subparams, sublogw, plain, gidx, key_data)

    def f(xs, params, subparams, logw, sublogw, active, key_data):
        gi = gibbs.global_indices(xs.shape[0], ("data",))
        lab = fam.assign(xs, params, logw, active, gi, key_data,
                         feat_axis="model")
        sub = fam.sub_assign(xs, subparams, sublogw, lab, gi, key_data,
                             feat_axis="model", chunk=16)
        return lab, sub

    rep = jax.tree.map(lambda _: P(), (params, subparams))
    got, sub_got = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("data", "model"), rep[0], rep[1], P(), P(), P(), P()),
        out_specs=(P("data"), P("data")), check_vma=False))(
            x, params, subparams, logw, sublogw, active, key_data)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(sub_got), np.asarray(sub_plain))


# ---------------------------------------------------------------------------
# structural guarantee: no (N, K, 2) intermediate anywhere in the sweep
# ---------------------------------------------------------------------------
def _walk_avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for p in eqn.params.values():
            yield from _walk_param(p)


def _walk_param(p):
    if isinstance(p, jex_core.ClosedJaxpr):
        yield from _walk_avals(p.jaxpr)
    elif isinstance(p, jex_core.Jaxpr):
        yield from _walk_avals(p)
    elif isinstance(p, (list, tuple)):
        for q in p:
            yield from _walk_param(q)


@pytest.mark.parametrize("name", ALL)
def test_sweep_jaxpr_has_no_all_k_subcluster_loglik(name):
    """Step (f) must not evaluate all K clusters' sub-logliks: the sweep's
    jaxpr (reference path — kernels are opaque anyway) contains no
    (N, k_max, 2) intermediate at all."""
    from repro.core.sampler import _init_local
    n, k_max, d = 96, 8, 3
    fam = get_family(name)
    rng = np.random.default_rng(0)
    x = jnp.asarray(_data(name, n, d, rng))
    valid = jnp.ones((n,), bool)
    cfg = DPMMConfig(component=name, init_clusters=3, k_max=k_max)
    prior = fam.build_prior(cfg, x)
    model, point = _init_local(jax.random.key(0), x, valid, prior=prior,
                               family=fam, cfg=cfg, axes=(), k_max=k_max)
    jaxpr = jax.make_jaxpr(
        lambda m, p, xx: gibbs.sweep(m, p, xx, prior, fam, 10.0, ()))(
            model, point, x)
    shapes = {tuple(a.shape) for a in _walk_avals(jaxpr.jaxpr)
              if hasattr(a, "shape")}
    assert (n, k_max, 2) not in shapes, (
        "found an (N, K, 2) intermediate: step (f) is evaluating all-K "
        "sub-cluster logliks again")
