"""DPMM serving path (ISSUE 5): ``DPMMEngine`` answers must be exactly
the sampler's math — soft assignment log-probs match ``family.loglik`` +
renormalized log-weights to f32 ULPs, hard labels are their argmax, the
sampled assignment is the sweep's counter-based Gumbel argmax — and the
fixed-batch precompiled step must make batching invisible (padding never
leaks into answers)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.scipy.special import logsumexp

from repro.configs import DPMMConfig
from repro.core.checkpoint import save_model
from repro.core.family import NEG_INF, get_family
from repro.core.sampler import DPMM
from repro.data.synthetic import generate_gmm
from repro.kernels import prng
from repro.serve import DPMMEngine, ServeConfig

N, D, K = 3000, 4, 4


@pytest.fixture(scope="module")
def fitted():
    # one draw from one mixture; the tail 1200 rows are held out of the
    # fit and served as queries (same components, unseen points)
    x_all, gt_all = generate_gmm(N + 1200, D, K, seed=0, sep=10.0)
    cfg = DPMMConfig(alpha=10.0, iters=16, k_max=16, burnout=4)
    result = DPMM(cfg).fit(x_all[:N], n_chains=2).select_best()
    return result, np.asarray(x_all[N:]), np.asarray(gt_all[N:])


def test_soft_assignment_matches_family_loglik(fitted):
    """The acceptance contract: engine soft-assignment == the assignment
    log-probs computed straight from family.loglik, to f32 ULPs."""
    result, xq, _ = fitted
    engine = DPMMEngine(result.state, "gaussian", ServeConfig(batch_sizes=(512,)))
    res = engine.query(xq)
    fam = get_family("gaussian")
    ll = fam.loglik(jnp.asarray(xq), result.state.params)
    logits = jnp.where(result.state.active[None, :],
                       ll + engine.logweights[None, :], NEG_INF)
    expect = np.asarray(logits - logsumexp(logits, axis=-1,
                                           keepdims=True))
    finite = np.isfinite(expect)
    np.testing.assert_allclose(res.logprobs[finite], expect[finite],
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(res.labels, np.asarray(logits).argmax(axis=1))
    # log-predictive is the logsumexp of the same logits, and soft
    # probs are normalized
    np.testing.assert_allclose(
        res.log_predictive, np.asarray(logsumexp(logits, axis=-1)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.exp(res.logprobs).sum(axis=1), 1.0, rtol=1e-4)


def test_batching_is_invisible(fitted):
    """Ragged tails are padded to the fixed compiled batch shape; the
    padding must never leak — any batch size gives the same answers."""
    result, xq, _ = fitted
    engines = [DPMMEngine(result.state, "gaussian", ServeConfig(batch_sizes=(b,)))
               for b in (256, 1200, 4096)]   # 1200 = exact, others ragged
    results = [e.query(xq) for e in engines]
    for other in results[1:]:
        assert np.array_equal(results[0].labels, other.labels)
        np.testing.assert_allclose(results[0].logprobs, other.logprobs,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(results[0].log_predictive,
                                   other.log_predictive,
                                   rtol=1e-5, atol=1e-5)


def test_predict_quality_and_outlier_scoring(fitted):
    """Served hard labels recover the generating clusters on held-out
    data; far-away points score lower predictive density."""
    result, xq, gtq = fitted
    engine = DPMMEngine(result.state, "gaussian", ServeConfig(batch_sizes=(512,)))
    from repro.core.metrics import nmi
    served_nmi = float(nmi(jnp.asarray(gtq),
                           jnp.asarray(engine.predict(xq)), K, 16))
    assert served_nmi > 0.9
    outliers = np.full((64, D), 1e3, np.float32)
    assert (engine.log_predictive(outliers).max()
            < engine.log_predictive(xq).min())


def test_checkpoint_engine_identical(fitted, tmp_path):
    """from_checkpoint must serve the EXACT model: same compiled shapes,
    bitwise-equal answers to the in-memory engine."""
    result, xq, _ = fitted
    path = str(tmp_path / "m.npz")
    save_model(path, result.state, "gaussian")
    mem = DPMMEngine(result.state, "gaussian", ServeConfig(batch_sizes=(512,)))
    ckpt = DPMMEngine.from_checkpoint(path, ServeConfig(batch_sizes=(512,)))
    a, b = mem.query(xq), ckpt.query(xq)
    assert np.array_equal(a.labels, b.labels)
    assert np.array_equal(a.logprobs, b.logprobs)
    assert np.array_equal(a.log_predictive, b.log_predictive)


def test_sample_reuses_sweep_assignment(fitted):
    """engine.sample is the sweep's step (e) verbatim: counter-based
    Gumbel argmax through family.assign with gidx = query row index."""
    result, xq, _ = fitted
    engine = DPMMEngine(result.state, "gaussian",
                        ServeConfig(batch_sizes=(int(xq.shape[0]),)))
    drawn = engine.sample(xq, seed=3)
    fam = get_family("gaussian")
    gidx = jnp.arange(xq.shape[0], dtype=jnp.uint32)
    expect = fam.assign(jnp.asarray(xq), result.state.params,
                        engine.logweights, result.state.active, gidx,
                        prng.key_words(jax.random.key(3)))
    assert np.array_equal(drawn, np.asarray(expect))
    # pinned seed is reproducible
    assert np.array_equal(drawn, engine.sample(xq, seed=3))
    # on AMBIGUOUS queries the draw genuinely samples (well-separated
    # points essentially never flip). Find a point on the decision
    # boundary between the two biggest clusters by line search on the
    # engine's own log-probs, then repeat it 512x: i.i.d. counter-based
    # draws per row must produce both labels, and the unpinned engine
    # key advances between calls.
    means = np.asarray(fam.cluster_means(result.state.stats))
    n_k = np.where(np.asarray(result.state.active),
                   np.asarray(result.state.stats.n), 0.0)
    a, b = np.argsort(n_k)[-2:]
    ts = np.linspace(0.0, 1.0, 2001)[:, None].astype(np.float32)
    seg = (1 - ts) * means[a] + ts * means[b]
    lp = engine.predict_logprobs(seg)
    top2 = np.sort(lp, axis=1)[:, -2:]
    boundary = seg[np.argmin(top2[:, 1] - top2[:, 0])]
    assert (top2[:, 1] - top2[:, 0]).min() < 2.0, "no ambiguous point"
    ambiguous = np.tile(boundary, (512, 1)).astype(np.float32)
    s1, s2 = engine.sample(ambiguous), engine.sample(ambiguous)
    assert len(np.unique(s1)) >= 2
    assert not np.array_equal(s1, s2)


def test_engine_guardrails(fitted):
    result, xq, _ = fitted
    multi = jax.tree.map(lambda v: v[None], result.state)
    with pytest.raises(ValueError, match="single-chain"):
        DPMMEngine(multi, "gaussian")
    with pytest.raises(ValueError, match="batch_size"):
        DPMMEngine(result.state, "gaussian",
                   ServeConfig(batch_sizes=(0,)))
    engine = DPMMEngine(result.state, "gaussian", ServeConfig(batch_sizes=(64,)))
    with pytest.raises(ValueError, match="queries must be"):
        engine.predict(np.zeros((10, D + 1), np.float32))


def test_serve_cli_roundtrip(fitted, tmp_path, capsys):
    """launch/serve_dpmm drives the engine off a real checkpoint file."""
    import json

    from repro.launch import serve_dpmm

    result, xq, _ = fitted
    ckpt = str(tmp_path / "cli.npz")
    save_model(ckpt, result.state, "gaussian")
    qpath = str(tmp_path / "q.npy")
    np.save(qpath, xq[:200])
    out = str(tmp_path / "out.json")
    serve_dpmm.main(["--checkpoint", ckpt, "--queries", qpath,
                     "--batch-sizes", "128", "--result-path", out])
    with open(out) as f:
        payload = json.load(f)
    assert len(payload["labels"]) == 200
    assert payload["family"] == "gaussian"
    engine = DPMMEngine(result.state, "gaussian", ServeConfig(batch_sizes=(128,)))
    assert np.array_equal(np.asarray(payload["labels"], np.int32),
                          engine.predict(xq[:200]))


def test_serve_cli_profile_dir_traces_the_engine_spans(fitted, tmp_path):
    """``--profile-dir`` writes a profiler trace whose host plane holds
    the engine's request span, with its row count."""
    from repro.launch import serve_dpmm

    result, xq, _ = fitted
    ckpt = str(tmp_path / "cli.npz")
    save_model(ckpt, result.state, "gaussian")
    qpath = str(tmp_path / "q.npy")
    np.save(qpath, xq[:200])
    serve_dpmm.main(["--checkpoint", ckpt, "--queries", qpath,
                     "--batch-sizes", "128",
                     "--profile-dir", str(tmp_path / "trace")])
    assert _host_spans(tmp_path / "trace", "dpmm.serve.query") == [
        {"rows": 200, "segments": 2}]


def _host_spans(trace_dir, name):
    """The arguments of the ``name`` spans on the host plane of the one
    profiler trace under ``trace_dir``, in the order they started."""
    import glob
    import warnings

    from jax.profiler import ProfileData

    paths = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        events = [(e.start_ns, dict(e.stats))
                  for plane in ProfileData.from_file(paths[0]).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name == name]
    return [stats for _, stats in sorted(events, key=lambda e: e[0])]


@pytest.mark.parametrize("n", [1, 127, 128, 129, 300])
def test_query_starts_every_copy_before_the_first_read(fitted, tmp_path, n):
    """Every output's copy to the host starts before any is read: the
    first ``copy_back`` span counts all of the request's copies in
    flight (three outputs a segment), the last one. The answers are
    bitwise those of the same steps read one output at a time."""
    result, xq, _ = fitted
    engine = DPMMEngine(result.state, "gaussian",
                        ServeConfig(batch_sizes=(128,)))
    x = xq[:n]
    with jax.profiler.trace(str(tmp_path / "trace")):
        got = engine.query(x)
    route = engine.plan_route(n)
    served = engine._served
    one_at_a_time = {"labels": [], "logprobs": [], "log_predictive": []}
    for start, used, b in route:
        out = served.steps[b](engine._pad(x[start:start + used], b, D),
                              *served.ops)
        for k, v in out.items():
            one_at_a_time[k].append(np.asarray(jax.device_get(v))[:used])
    copies = _host_spans(tmp_path / "trace", "dpmm.serve.copy_back")
    assert [c["inflight"] for c in copies] == list(
        range(3 * len(route), 0, -1))
    assert [c["out"] for c in copies] == list(out) * len(route)
    for k, parts in one_at_a_time.items():
        want = np.concatenate(parts)
        assert getattr(got, k).dtype == want.dtype
        assert np.array_equal(getattr(got, k), want), k
