"""Sparse-K sweeps (ISSUE 6): active-set compaction + K-blocked
megakernels make per-iteration cost O(K_active) and lift the all-K-in-VMEM
ceiling — as a PURE performance change.

 - tile-level parity: ``gibbs.sweep_tile`` on a compacted slab (with the
   K-blocked kernel at two block sizes) vs the dense slab, BITWISE
   (labels, sublabels, scattered stats) for all 4 families on both the
   jnp reference and Pallas (interpret) paths;
 - full-fit parity: ``compact=True`` fits (the default) are bitwise
   ``compact=False`` fits on the resident AND tiled planes, all families;
 - the k_max >= 512 acceptance fit: a compacted K-blocked megakernel fit
   under a 512-slot slab matches the dense-slab jnp reference at every
   iteration (labels + history; score to the cross-path float tolerance);
 - the structural sparse-K guarantee: the megakernel's cluster-parameter
   operands are (k_block, ...)-tiled in the pallas_call grid — no
   (k_max, ...)-resident block exists, so VMEM per grid step is O(bk);
 - the ``k_max='auto'`` growth hook and its config validation.
"""
import numpy as np
import pytest

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from repro.configs import DPMMConfig
from repro.core import gibbs
from repro.core.family import available_families, get_family
from repro.core.gibbs import STATS_BLOCK
from repro.core.sampler import DPMM, _init_local
from repro.data.synthetic import generate_gmm, generate_mnmm, generate_pmm

ALL = available_families()
K_BLOCKS = (4, 8)


def _data(name, n, d=5, k=4):
    if name in ("gaussian", "diag_gaussian"):
        return generate_gmm(n, d, k, seed=0, sep=8.0)[0]
    if name == "poisson":
        return generate_pmm(n, d, k, seed=0)[0]
    return generate_mnmm(n, max(d, k), k, seed=0)[0]


def _state(name, n, d=5, k_max=12, init_clusters=4):
    fam = get_family(name)
    x = jnp.asarray(_data(name, n, d))
    valid = jnp.ones((n,), jnp.float32)
    cfg = DPMMConfig(component=name, init_clusters=init_clusters,
                     k_max=k_max)
    prior = fam.build_prior(cfg, x)
    model, point = _init_local(jax.random.key(0), x, valid, prior=prior,
                               family=fam, cfg=cfg, axes=(), k_max=k_max)
    return fam, x, model, point, prior


def _run_tile(fam, x, model, point, use_pallas, plan=None, k_block=None):
    k = (model.active.shape[0] if plan is None
         else plan.slot_of_compact.shape[0])
    gidx = jnp.arange(x.shape[0], dtype=jnp.uint32)
    acc = gibbs.empty_substats(fam, k, x.shape[1])
    fn = jax.jit(lambda m, xx, p, g, a: gibbs.sweep_tile(
        m, xx, p, g, a, fam, use_pallas=use_pallas, plan=plan,
        k_block=k_block))
    point2, acc2 = fn(model, x, point, gidx, acc)
    if plan is not None:     # back to the dense slab for comparison
        acc2 = gibbs.compact_scatter(plan, model.active.shape[0], acc2)
    return jax.tree.map(np.asarray, (point2, acc2))


def _assert_tree_equal(a, b, what):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"{what}: stat leaves differ")


# ---------------------------------------------------------------------------
# tile-level: compacted K-blocked sweep == dense-slab sweep, bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k_block", K_BLOCKS)
@pytest.mark.parametrize("name", ALL)
def test_compact_tile_matches_dense_reference(name, k_block):
    """jnp path: the compacted sweep_tile (gather -> sweep -> scatter,
    slot-id Gumbel counters) reproduces the dense-slab sweep bitwise."""
    fam, x, model, point, _ = _state(name, STATS_BLOCK + 452)
    plan = gibbs.compaction_plan(model.active, 6)       # k_hat = 4 <= 6
    pd, ad = _run_tile(fam, x, model, point, use_pallas=False)
    pc, ac = _run_tile(fam, x, model, point, use_pallas=False, plan=plan,
                       k_block=k_block)
    np.testing.assert_array_equal(pc.labels, pd.labels)
    np.testing.assert_array_equal(pc.sublabels, pd.sublabels)
    _assert_tree_equal(ac, ad, f"{name} bk={k_block} reference")


@pytest.mark.parametrize("k_block", K_BLOCKS)
@pytest.mark.parametrize("name", ALL)
def test_compact_tile_matches_dense_pallas(name, k_block):
    """Pallas (interpret) path: the compacted K-blocked megakernel —
    streaming (k_block, ...) cluster tiles with a running argmax carry —
    reproduces the dense-slab megakernel bitwise."""
    fam, x, model, point, _ = _state(name, STATS_BLOCK + 452)
    plan = gibbs.compaction_plan(model.active, 6)
    pd, ad = _run_tile(fam, x, model, point, use_pallas=True)
    pc, ac = _run_tile(fam, x, model, point, use_pallas=True, plan=plan,
                       k_block=k_block)
    np.testing.assert_array_equal(pc.labels, pd.labels)
    np.testing.assert_array_equal(pc.sublabels, pd.sublabels)
    _assert_tree_equal(ac, ad, f"{name} bk={k_block} pallas")


# ---------------------------------------------------------------------------
# full-fit parity: compact=True (default) == compact=False, both planes
# ---------------------------------------------------------------------------
def _cfg(name, **kw):
    return DPMMConfig(component=name, alpha=10.0, iters=14, k_max=16,
                      burnout=4, **kw)


def _assert_fit_bitwise(a, b, what):
    assert np.array_equal(a.labels, b.labels), f"{what}: labels differ"
    for key in a.history:
        assert np.array_equal(a.history[key], b.history[key]), (
            f"{what}: history[{key}] differs")
    for field in ("stats", "substats"):
        _assert_tree_equal(getattr(a.state, field),
                           getattr(b.state, field), f"{what}: {field}")


@pytest.mark.parametrize("name", ALL)
def test_compact_fit_matches_dense_both_planes(name):
    """Full DPMM.fit: compaction (2x-headroom pow2 slabs, lax.cond dense
    fallback, split/merge compact fold) is chain-neutral on the resident
    plane, and the tiled plane (per-iteration exact k_c, no cond) matches
    too."""
    x = _data(name, 2048, d=4)
    dense = DPMM(_cfg(name, compact=False)).fit(x)
    assert dense.k >= 2               # non-trivial chain: splits happened
    compact = DPMM(_cfg(name, compact=True)).fit(x)
    _assert_fit_bitwise(dense, compact, f"{name} resident")
    tiled = DPMM(_cfg(name, compact=True,
                      tile_size=STATS_BLOCK)).fit(x)
    _assert_fit_bitwise(dense, tiled, f"{name} tiled-compact")


def test_compact_fit_matches_dense_multichain():
    x = _data("gaussian", 2048, d=4)
    dense = DPMM(_cfg("gaussian", compact=False)).fit(x, n_chains=2)
    compact = DPMM(_cfg("gaussian", compact=True)).fit(x, n_chains=2)
    _assert_fit_bitwise(dense, compact, "multichain")


# ---------------------------------------------------------------------------
# the k_max >= 512 acceptance fit (ISSUE 6)
# ---------------------------------------------------------------------------
def _cfg512(**kw):
    # burnout == iters: no split/merge, so k stays at init_clusters and
    # the O(K^2) merge proposal never runs at K=512 (the sweep itself is
    # the object under test); init_clusters=6 keeps 6 live clusters under
    # the 512-slot slab -> compact slab = 16 pow2 rows
    return DPMMConfig(component="gaussian", alpha=10.0, iters=6,
                      k_max=512, init_clusters=6, burnout=6, log_every=3,
                      **kw)


def test_kmax_512_compact_jnp_matches_dense_bitwise():
    """Under a 512-slot slab, the compacted jnp fit is bitwise the dense
    jnp fit at every iteration (history rows) and in the final state."""
    x = _data("gaussian", 1024, d=4)
    dense = DPMM(_cfg512(compact=False)).fit(x)
    compact = DPMM(_cfg512(compact=True)).fit(x)
    _assert_fit_bitwise(dense, compact, "k_max=512 jnp")


def test_kmax_512_megakernel_matches_dense_reference():
    """The acceptance fit: k_max=512 through the compacted K-blocked
    megakernel (interpret mode on CPU) vs the dense-slab jnp reference.
    Labels and the k/cluster-size history match bitwise at every
    iteration; the 'score' trace — a float function of differently-
    associated stat sums — matches to the repo's cross-path tolerance."""
    x = _data("gaussian", 1024, d=4)
    dense = DPMM(_cfg512(compact=False, use_pallas=False)).fit(x)
    fused = DPMM(_cfg512(compact=True, use_pallas=True)).fit(x)
    assert np.array_equal(fused.labels, dense.labels)
    for key in ("k", "max_cluster", "min_cluster"):
        assert np.array_equal(fused.history[key], dense.history[key]), key
    np.testing.assert_allclose(fused.history["score"],
                               dense.history["score"], rtol=1e-3, atol=1.0)


# ---------------------------------------------------------------------------
# structural: the megakernel streams (k_block, ...) cluster tiles
# ---------------------------------------------------------------------------
def _find_pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for p in eqn.params.values():
            for q in (p if isinstance(p, (list, tuple)) else (p,)):
                if isinstance(q, jex_core.ClosedJaxpr):
                    _find_pallas_calls(q.jaxpr, out)
                elif isinstance(q, jex_core.Jaxpr):
                    _find_pallas_calls(q, out)
    return out


def _block_dims(block_mapping):
    """Integer extents of a block (``Blocked(n)`` entries; squeezed dims
    have none)."""
    return [getattr(d, "block_size", d) for d in block_mapping.block_shape
            if isinstance(getattr(d, "block_size", d), int)]


@pytest.mark.parametrize("name", ("gaussian", "multinomial"))
def test_megakernel_params_are_k_block_tiled(name):
    """The pallas_call grid carries a K-block axis and NO operand block
    is (k_max, ...)-resident: every block dim is <= max(bn, 2 * k_max //
    gk) — VMEM per grid step is O(bn + bk), independent of k_max. This is
    what removes the all-K SUB_PARAMS_VMEM ceiling."""
    k_max, bk = 512, 8
    fam, x, model, point, _ = _state(name, 256, d=4, k_max=k_max,
                                     init_clusters=6)
    gidx = jnp.arange(x.shape[0], dtype=jnp.uint32)
    acc = gibbs.empty_substats(fam, k_max, x.shape[1])
    jaxpr = jax.make_jaxpr(
        lambda m, xx, p, g, a: gibbs.sweep_tile(
            m, xx, p, g, a, fam, use_pallas=True, k_block=bk))(
        model, x, point, gidx, acc)
    calls = _find_pallas_calls(jaxpr.jaxpr, [])
    assert len(calls) == 1, "sweep must be ONE megakernel"
    gm = calls[0].params["grid_mapping"]
    grid = tuple(gm.grid)
    assert len(grid) == 3 and grid[1] == 2 and grid[2] == k_max // bk, (
        f"expected (gn, 2, {k_max // bk}) grid, got {grid}")
    for bm in gm.block_mappings:
        dims = _block_dims(bm)
        assert dims, f"no block extents read from {bm.block_shape}"
        assert k_max not in dims and 2 * k_max not in dims, (
            f"(k_max, ...)-resident block {bm.block_shape}: the kernel "
            "must stream K-blocks, not hold the full slab in VMEM")


# ---------------------------------------------------------------------------
# k_max='auto': the slab is a discovered high-water mark
# ---------------------------------------------------------------------------
def test_auto_k_max_grows_and_clusters():
    x, gt = generate_gmm(4096, 4, 5, seed=0, sep=10.0)
    cfg = DPMMConfig(alpha=10.0, iters=20, k_max="auto", k_max_cap=64,
                     init_clusters=1, burnout=5, log_every=4)
    r = DPMM(cfg).fit(x)
    # started at the 8-slot floor; the 5-cluster posterior forces growth
    assert r.state.active.shape[0] > 8
    assert r.state.active.shape[0] <= 64
    assert r.k >= 4 and r.nmi(gt) > 0.9


def test_auto_k_max_deterministic():
    """Same config -> same chain: growth points depend only on the chain,
    which depends only on (seed, schedule)."""
    x, _ = generate_gmm(2048, 3, 4, seed=1, sep=10.0)
    cfg = DPMMConfig(alpha=10.0, iters=14, k_max="auto", k_max_cap=32,
                     burnout=4, log_every=5)
    a, b = DPMM(cfg).fit(x), DPMM(cfg).fit(x)
    assert np.array_equal(a.labels, b.labels)
    for key in a.history:
        assert np.array_equal(a.history[key], b.history[key])


def test_auto_k_max_config_validation():
    with pytest.raises(ValueError, match="resident"):
        DPMMConfig(k_max="auto", tile_size=1024)
    with pytest.raises(ValueError, match="k_max_cap"):
        DPMMConfig(k_max="auto", k_max_cap=0)
    with pytest.raises(ValueError, match="k_block"):
        DPMMConfig(k_block=0)
    with pytest.raises(ValueError, match="k_max"):
        DPMMConfig(k_max=0)


def test_auto_k_max_rejected_on_tiled_source(tmp_path):
    """A non-resident DataSource forces the tiled driver even with
    tile_size=None — 'auto' must fail loudly there, not mis-run."""
    from repro.data.source import HostTiledSource
    x, _ = generate_gmm(1024, 3, 3, seed=0, sep=10.0)
    path = tmp_path / "x.npy"
    np.save(path, x.astype(np.float32))
    src = HostTiledSource.from_npy(str(path))
    with pytest.raises(ValueError, match="resident"):
        DPMM(DPMMConfig(k_max="auto", iters=2)).fit(src)


# ---------------------------------------------------------------------------
# compacted serving engine: bitwise the dense engine math
# ---------------------------------------------------------------------------
def test_serve_engine_compacts_and_matches_dense_math():
    from repro.core.family import NEG_INF
    from repro.serve.dpmm import DPMMEngine, ServeConfig

    x, _ = generate_gmm(2048, 3, 4, seed=2, sep=10.0)
    st = DPMM(_cfg("gaussian")).fit(x).state
    eng = DPMMEngine(st, "gaussian", ServeConfig(batch_sizes=(128,)))
    assert eng.k_active == int(np.asarray(st.active).sum())
    assert eng.k_active < eng.k_max       # compaction actually engaged
    q = np.asarray(x[:300])
    res = eng.query(q)
    # dense reference math over the full slab
    fam = eng.family
    logw = jnp.where(st.active, st.logweights, NEG_INF)
    logw = (logw - jax.scipy.special.logsumexp(
        jnp.where(st.active, logw, -jnp.inf))).astype(jnp.float32)
    ll = fam.loglik(jnp.asarray(q), st.params)
    logits = jnp.where(st.active[None, :], ll + logw[None, :], NEG_INF)
    logpred = jax.scipy.special.logsumexp(logits, axis=-1)
    np.testing.assert_array_equal(
        res.labels, np.asarray(jnp.argmax(logits, -1), np.int32))
    np.testing.assert_array_equal(res.log_predictive, np.asarray(logpred))
    np.testing.assert_array_equal(
        res.logprobs, np.asarray(logits - logpred[:, None]))
    # sampled draws live on active slots and reproduce under a pinned seed
    s = eng.sample(q, seed=3)
    np.testing.assert_array_equal(s, eng.sample(q, seed=3))
    assert set(np.unique(s)).issubset(set(eng.slots.tolist()))
