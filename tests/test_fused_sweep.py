"""One-read fused sweep (ISSUE 4): steps (e) + (f) + the suff-stat fold
run in a single pass over x — and the fusion is a pure performance change.

 - tile-level parity: ``gibbs.sweep_tile`` fused vs the pre-PR three-pass
   body, BITWISE (labels, sublabels, folded substats) for all 4 families
   on aligned, ragged and sub-block tile lengths, on both the jnp
   reference path and the Pallas megakernel (interpret) path;
 - full-fit parity: fused chains (labels, history, stats, substats)
   bitwise identical to three-pass chains on the resident, tiled,
   data-sharded and feature-sharded planes, at two tile sizes;
 - the structural one-read guarantee: the reference sweep's jaxpr
   consumes x in exactly ONE (blocked) scan, and the Pallas sweep's jaxpr
   contains exactly ONE pallas_call — nothing re-reads x;
 - the fused split/merge apply matches its three-pass form bitwise.
"""
import functools

import numpy as np
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import pytest

from repro.configs import DPMMConfig
from repro.core import gibbs, splitmerge
from repro.core.family import available_families, get_family
from repro.core.gibbs import STATS_BLOCK
from repro.core.sampler import DPMM, _init_local, _move_key
from repro.data.synthetic import generate_gmm, generate_mnmm, generate_pmm

ALL = available_families()
SHARDABLE = [n for n in ALL if get_family(n).feature_shardable]
# aligned (2 blocks), ragged (2 blocks + tail), sub-block (tail only)
TILE_NS = (2 * STATS_BLOCK, 2 * STATS_BLOCK + 452, 700)


def _data(name, n, d=5, k=4):
    if name in ("gaussian", "diag_gaussian"):
        return generate_gmm(n, d, k, seed=0, sep=8.0)[0]
    if name == "poisson":
        return generate_pmm(n, d, k, seed=0)[0]
    return generate_mnmm(n, max(d, k), k, seed=0)[0]


def _state(name, n, d=5, k_max=12):
    fam = get_family(name)
    x = jnp.asarray(_data(name, n, d))
    valid = jnp.ones((n,), jnp.float32)
    cfg = DPMMConfig(component=name, init_clusters=4, k_max=k_max)
    prior = fam.build_prior(cfg, x)
    model, point = _init_local(jax.random.key(0), x, valid, prior=prior,
                               family=fam, cfg=cfg, axes=(), k_max=k_max)
    return fam, x, model, point, prior


def _run_tile(fam, x, model, point, fused, use_pallas):
    k_max = model.active.shape[0]
    gidx = jnp.arange(x.shape[0], dtype=jnp.uint32)
    acc = gibbs.empty_substats(fam, k_max, x.shape[1])
    fn = jax.jit(lambda m, xx, p, g, a: gibbs.sweep_tile(
        m, xx, p, g, a, fam, fused=fused, use_pallas=use_pallas))
    return jax.tree.map(np.asarray, fn(model, x, point, gidx, acc))


def _assert_tree_equal(a, b, what):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"{what}: stat leaves differ")


# ---------------------------------------------------------------------------
# tile-level: fused == three-pass, bitwise, per path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", TILE_NS)
@pytest.mark.parametrize("name", ALL)
def test_sweep_tile_fused_matches_three_pass(name, n):
    fam, x, model, point, _ = _state(name, n)
    p3, a3 = _run_tile(fam, x, model, point, fused=False, use_pallas=False)
    pf, af = _run_tile(fam, x, model, point, fused=True, use_pallas=False)
    np.testing.assert_array_equal(pf.labels, p3.labels)
    np.testing.assert_array_equal(pf.sublabels, p3.sublabels)
    _assert_tree_equal(af, a3, f"{name} n={n} reference")


@pytest.mark.parametrize("n", TILE_NS)
@pytest.mark.parametrize("name", ALL)
def test_sweep_tile_fused_pallas_matches_three_pass_pallas(name, n):
    """The megakernel (interpret mode) reproduces the three-pass Pallas
    chain bitwise — assignment, sub-assignment AND the stat fold."""
    fam, x, model, point, _ = _state(name, n)
    p3, a3 = _run_tile(fam, x, model, point, fused=False, use_pallas=True)
    pf, af = _run_tile(fam, x, model, point, fused=True, use_pallas=True)
    np.testing.assert_array_equal(pf.labels, p3.labels)
    np.testing.assert_array_equal(pf.sublabels, p3.sublabels)
    _assert_tree_equal(af, a3, f"{name} n={n} pallas")


@pytest.mark.parametrize("name", ALL)
def test_sweep_megakernel_labels_match_reference(name):
    """Cross-path: megakernel labels/sublabels equal the jnp reference's
    (same counter-based noise); stats agree to float tolerance (the two
    paths associate the per-block sums differently — pre-existing)."""
    fam, x, model, point, _ = _state(name, 2 * STATS_BLOCK + 452)
    pr, ar = _run_tile(fam, x, model, point, fused=True, use_pallas=False)
    pp, ap = _run_tile(fam, x, model, point, fused=True, use_pallas=True)
    np.testing.assert_array_equal(pp.labels, pr.labels)
    np.testing.assert_array_equal(pp.sublabels, pr.sublabels)
    for la, lb in zip(jax.tree_util.tree_leaves(ar),
                      jax.tree_util.tree_leaves(ap)):
        np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("name", ("gaussian", "multinomial"))
def test_sweep_megakernel_chunked_matches_one_call(name, monkeypatch):
    """A megakernel sweep run in STATS_BLOCK chunks (two in the loop, then
    a ragged tail) folds the same partials in the same order as one call
    over the tile: bitwise equal labels, sublabels and stats."""
    from repro.kernels import ops
    fam, x, model, point, _ = _state(name, 2 * STATS_BLOCK + 452)
    p1, a1 = _run_tile(fam, x, model, point, fused=True, use_pallas=True)
    monkeypatch.setattr(ops, "SWEEP_PARTIALS_BYTES", 1)
    assert ops.sweep_chunk_points(1 << 20) == STATS_BLOCK
    pc, ac = _run_tile(fam, x, model, point, fused=True, use_pallas=True)
    np.testing.assert_array_equal(pc.labels, p1.labels)
    np.testing.assert_array_equal(pc.sublabels, p1.sublabels)
    _assert_tree_equal(ac, a1, f"{name} chunked")


# ---------------------------------------------------------------------------
# fused split/merge apply == three-pass apply
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", TILE_NS)
@pytest.mark.parametrize("name", ("gaussian", "multinomial"))
def test_split_merge_tile_fused_matches_three_pass(name, n):
    fam, x, model, point, prior = _state(name, n)
    k_max = model.active.shape[0]
    plan = splitmerge.plan_split_merge(_move_key(model), model, prior, fam,
                                       10.0, 10)

    def run(fused):
        acc = gibbs.empty_substats(fam, k_max, x.shape[1])
        fn = jax.jit(lambda pl_, xx, p, a: splitmerge.split_merge_tile(
            pl_, xx, p, a, fam, fused=fused))
        return jax.tree.map(np.asarray, fn(plan, x, point, acc))

    p3, a3 = run(False)
    pf, af = run(True)
    np.testing.assert_array_equal(pf.labels, p3.labels)
    np.testing.assert_array_equal(pf.sublabels, p3.sublabels)
    _assert_tree_equal(af, a3, f"{name} n={n} split_merge")


# ---------------------------------------------------------------------------
# full-fit parity across planes: fused chains == three-pass chains
# ---------------------------------------------------------------------------
def _cfg(name, **kw):
    return DPMMConfig(component=name, alpha=10.0, iters=14, k_max=16,
                      burnout=4, **kw)


def _fit_data(name):
    if name in ("gaussian", "diag_gaussian"):
        return generate_gmm(2 * STATS_BLOCK + 600, 4, 4, seed=0, sep=10.0)
    if name == "poisson":
        return generate_pmm(2 * STATS_BLOCK + 600, 4, 4, seed=0)
    return generate_mnmm(2 * STATS_BLOCK + 600, 16, 4, seed=0)


def _assert_fit_bitwise(a, b, what):
    assert np.array_equal(a.labels, b.labels), f"{what}: labels differ"
    for key in a.history:
        assert np.array_equal(a.history[key], b.history[key]), (
            f"{what}: history[{key}] differs")
    for stat in ("stats", "substats"):
        _assert_tree_equal(getattr(a.state, stat), getattr(b.state, stat),
                           f"{what}: {stat}")


@pytest.mark.parametrize("name", ALL)
def test_fit_fused_matches_three_pass_chains(name):
    """Run the three-pass fit inside a local patch, the fused fits
    outside, and require bitwise-identical chains — resident plane plus
    the tiled plane at two tile sizes."""
    x, _ = _fit_data(name)
    fused = DPMM(_cfg(name)).fit(x)
    assert fused.k >= 2                     # a non-trivial chain
    orig_sweep, orig_sm = gibbs.sweep_tile, splitmerge.split_merge_tile
    gibbs.sweep_tile = functools.partial(orig_sweep, fused=False)
    splitmerge.split_merge_tile = functools.partial(orig_sm, fused=False)
    try:
        three = DPMM(_cfg(name)).fit(x)
    finally:
        gibbs.sweep_tile, splitmerge.split_merge_tile = orig_sweep, orig_sm
    _assert_fit_bitwise(fused, three, f"{name} resident")
    for tile in (STATS_BLOCK, 2 * STATS_BLOCK):
        fused_tiled = DPMM(_cfg(name, tile_size=tile)).fit(x)
        _assert_fit_bitwise(fused_tiled, three, f"{name} tiled={tile}")


def test_fit_fused_matches_three_pass_sharded():
    """Data-sharded plane (all devices): fused == three-pass bitwise."""
    from repro.core.distributed import make_data_mesh
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (conftest sets 4 virtual devices)")
    x, _ = _fit_data("gaussian")
    mesh = make_data_mesh(jax.device_count())
    fused = DPMM(_cfg("gaussian"), mesh=mesh).fit(x)
    orig_sweep, orig_sm = gibbs.sweep_tile, splitmerge.split_merge_tile
    gibbs.sweep_tile = functools.partial(orig_sweep, fused=False)
    splitmerge.split_merge_tile = functools.partial(orig_sm, fused=False)
    try:
        three = DPMM(_cfg("gaussian"), mesh=mesh).fit(x)
    finally:
        gibbs.sweep_tile, splitmerge.split_merge_tile = orig_sweep, orig_sm
    _assert_fit_bitwise(fused, three, "gaussian sharded")


def test_fit_fused_matches_three_pass_feature_sharded():
    """Feature-sharded plane (2x2 mesh): the blocked one-read pass psums
    its per-block likelihood partials; chains still match bitwise."""
    from jax.sharding import Mesh
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    x, _ = generate_mnmm(2000, 32, 5, seed=1)
    mesh22 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                  ("data", "model"))
    cfg = _cfg("multinomial", shard_features=True)
    fused = DPMM(cfg, mesh=mesh22).fit(x)
    orig_sweep, orig_sm = gibbs.sweep_tile, splitmerge.split_merge_tile
    gibbs.sweep_tile = functools.partial(orig_sweep, fused=False)
    splitmerge.split_merge_tile = functools.partial(orig_sm, fused=False)
    try:
        three = DPMM(cfg, mesh=mesh22).fit(x)
    finally:
        gibbs.sweep_tile, splitmerge.split_merge_tile = orig_sweep, orig_sm
    _assert_fit_bitwise(fused, three, "multinomial feature-sharded")


def test_fit_fused_pallas_matches_three_pass_pallas():
    """Full fits through the megakernel (interpret) reproduce the
    three-pass Pallas chain bitwise."""
    x, _ = generate_gmm(STATS_BLOCK + 600, 3, 4, seed=0, sep=10.0)
    cfg = _cfg("gaussian", use_pallas=True)
    fused = DPMM(cfg).fit(x)
    orig_sweep, orig_sm = gibbs.sweep_tile, splitmerge.split_merge_tile
    gibbs.sweep_tile = functools.partial(orig_sweep, fused=False)
    splitmerge.split_merge_tile = functools.partial(orig_sm, fused=False)
    try:
        three = DPMM(cfg).fit(x)
    finally:
        gibbs.sweep_tile, splitmerge.split_merge_tile = orig_sweep, orig_sm
    _assert_fit_bitwise(fused, three, "gaussian pallas")


# ---------------------------------------------------------------------------
# the structural one-read guarantee (jaxpr/HLO inspection)
# ---------------------------------------------------------------------------
def _sweep_jaxpr(name, n, use_pallas):
    fam, x, model, point, prior = _state(name, n)
    jaxpr = jax.make_jaxpr(
        lambda m, p, xx: gibbs.sweep(m, p, xx, prior, fam, 10.0, (),
                                     use_pallas=use_pallas))(model, point, x)
    x_var = jaxpr.jaxpr.invars[-1]
    return jaxpr.jaxpr, x_var


def _consumers(jaxpr, var):
    return [eqn for eqn in jaxpr.eqns if any(v is var for v in eqn.invars)]


@pytest.mark.parametrize("name", ALL)
def test_reference_sweep_reads_x_once(name):
    """The fused reference sweep consumes x in exactly one place: the
    block reshape feeding a single scan (e + f + stat fold per block) —
    the one-read structure, provable from the jaxpr."""
    jaxpr, x_var = _sweep_jaxpr(name, 2 * STATS_BLOCK, use_pallas=False)
    direct = _consumers(jaxpr, x_var)
    assert len(direct) == 1, (
        f"x is consumed by {len(direct)} top-level eqns "
        f"({[e.primitive.name for e in direct]}); expected the single "
        "block reshape of the one-read scan")
    assert direct[0].primitive.name == "reshape"
    blocked = direct[0].outvars[0]
    scans = _consumers(jaxpr, blocked)
    assert len(scans) == 1 and scans[0].primitive.name == "scan", (
        f"blocked x feeds {[e.primitive.name for e in scans]}; expected "
        "exactly one scan")


def _count_pallas_calls(jaxpr):
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count += 1
        for p in eqn.params.values():
            count += _count_pallas_param(p)
    return count


def _count_pallas_param(p):
    if isinstance(p, jex_core.ClosedJaxpr):
        return _count_pallas_calls(p.jaxpr)
    if isinstance(p, jex_core.Jaxpr):
        return _count_pallas_calls(p)
    if isinstance(p, (list, tuple)):
        return sum(_count_pallas_param(q) for q in p)
    return 0


@pytest.mark.parametrize("name", ALL)
def test_pallas_sweep_is_one_megakernel(name):
    """With use_pallas the whole sweep is ONE pallas_call (the megakernel
    carries e + f + the stat fold); the three-pass body needs several."""
    jaxpr, x_var = _sweep_jaxpr(name, 2 * STATS_BLOCK, use_pallas=True)
    assert _count_pallas_calls(jaxpr) == 1
    if name != "diag_gaussian":     # diag packs [x, x^2] before the call
        direct = _consumers(jaxpr, x_var)
        assert len(direct) == 1, (
            f"x is consumed by {len(direct)} eqns "
            f"({[e.primitive.name for e in direct]}); expected only the "
            "megakernel call")
        # the single consumer is the (jit-wrapped) megakernel call itself
        assert direct[0].primitive.name in ("pallas_call", "jit")
        assert _count_pallas_param(list(direct[0].params.values())) == 1


def test_three_pass_sweep_reads_x_many_times():
    """The contrast that makes the one-read claim meaningful: the pre-PR
    three-pass body consumes x from more than one top-level eqn."""
    fam, x, model, point, prior = _state("gaussian", 2 * STATS_BLOCK)
    gidx = jnp.arange(x.shape[0], dtype=jnp.uint32)
    acc = gibbs.empty_substats(fam, model.active.shape[0], x.shape[1])
    jaxpr = jax.make_jaxpr(
        lambda m, xx, p, g, a: gibbs.sweep_tile(
            m, xx, p, g, a, fam, fused=False))(model, x, point, gidx, acc)
    x_vars = [v for v in jaxpr.jaxpr.invars
              if getattr(v.aval, "shape", None) == x.shape]
    assert len(x_vars) == 1
    assert len(_consumers(jaxpr.jaxpr, x_vars[0])) >= 3
