#!/usr/bin/env python3
"""Smoke run of the DPMM sampler and its assignment server on a TPU.

    python chip_smoke.py                # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips   # 4-chip data-sharded fit vs 1 chip

Drives the system only through ``DPMM.fit``, ``save_model`` and
``DPMMEngine``, on data generated from ``--seed``, pinned to one device:

 (a) a resident Gaussian fit at the top of the paper's synthetic grid
     (N=1e6, d=32, K=16, k_max=64, 40 iterations), once on the jnp
     reference sweep and once on the Pallas megakernel;
 (b) a multinomial fit at the bag-of-words width (N=2e5, d=512, K=16)
     through the linear megakernel;
 (c) the streamed (tiled) plane on (a)'s data, continuing (a)'s chain;
 (d) (a)'s model saved and served by ``DPMMEngine`` over the default AOT
     ladder: requests of 256, 2048 and 8192 rows, plus one sampled draw.

Every phase checks its own result (thresholds and their reasons sit
beside the checks) and any failure exits non-zero. Off the TPU the script
fails before it fits anything. The figures printed before the last line
are smoke-run figures, not benchmark results; the last line is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

K_TRUE = 16
GAUSS = dict(n=1_000_000, d=32)        # FULL_GAUSS_GRID's top: N, d
MULT = dict(n=200_000, d=512)          # bench_real_data's bag-of-words d
ITERS = 40
TILE = 1 << 18
LADDER_REQUESTS = (256, 2048, 8192)

# Thresholds. The generated clusters are well apart (means ~6 sd apart per
# coordinate in d=32; sparse Dirichlet topics over 512 words at 50 draws a
# row), so a fit lands on the generating partition up to a few pieces
# that 40 iterations have not merged back. On a v5e at N=1e6 the Gaussian
# fit ends at K=19 with NMI 0.987-0.990: the 16 true clusters, one
# singleton and two uneven splits of true clusters — the same K on the jnp
# path at default and at highest matmul precision and on the Pallas path,
# so it is the chain at 40 iterations, not numerics.
NMI_MIN = 0.98            # those pieces cost ~0.01
K_WINDOW = (K_TRUE, K_TRUE + 4)   # every true cluster, plus a few pieces
# Splits cost NMI; a merge can cost less (the two smallest true clusters
# merged still score ~0.99), so recovery is also held to purity: each
# found cluster's points belong to one true cluster, where a merge would
# misplace at least the smallest true cluster, 2.5% of the points; and
# every true cluster must own a found cluster.
PURITY_MIN = 0.99
# The Pallas and jnp fits are two chains of the same posterior: a TPU f32
# dot rounds through one bf16 pass, differently in XLA and in Mosaic, so
# the chains are not bitwise equal. They agree on the true clusters and
# differ in where the unmerged pieces fall (0.76% of points on a v5e);
# losing a true cluster would move at least the smallest one, 2.5% of
# the points.
AGREE_MIN = 0.98
# Served argmax labels against the fit's last Gibbs draw, and sampled
# against argmax labels: points on the border between the unmerged pieces
# of a split true cluster have no clear owner, so a draw and the argmax
# part on them (on a v5e, three more sweeps moved 3.1% of the points).
# The served labels are also held to PURITY_MIN against the truth.
SERVE_AGREE_MIN = 0.95


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def _table(a, b):
    import numpy as np
    a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
    table = np.zeros((a.max() + 1, b.max() + 1), np.int64)
    np.add.at(table, (a, b), 1)
    return table


def agreement(a, b) -> float:
    """Share of points whose label in ``a`` maps to their label in ``b``
    under the majority map of ``a``'s clusters onto ``b``'s (slot ids of
    two chains need not coincide)."""
    table = _table(a, b)
    return float(table.max(axis=1).sum() / table.sum())


def covers(found, truth) -> bool:
    """Every true cluster holds the majority of some found cluster."""
    table = _table(found, truth)
    owners = table.argmax(axis=1)[table.sum(axis=1) > 0]
    return set(owners.tolist()) >= set(range(table.shape[1]))


@contextlib.contextmanager
def compiled_programs():
    """Collect the text of every AOT-compiled program (the resident
    driver compiles its chunks through ``Lowered.compile``)."""
    import jax
    texts = []
    original = jax.stages.Lowered.compile

    def compile_and_keep(self, *args, **kwargs):
        out = original(self, *args, **kwargs)
        texts.append(out.as_text())
        return out

    jax.stages.Lowered.compile = compile_and_keep
    try:
        yield texts
    finally:
        jax.stages.Lowered.compile = original


def report(phase: str, r, wall: float, nmi=None) -> None:
    mem = r.device_bytes or {}
    steady = r.iter_times_s[ITERS // 4:] or r.iter_times_s
    say(f"[smoke figure, not a benchmark] {phase}: wall {wall:.1f} s, "
        f"set-up+compile {wall - sum(r.iter_times_s):.1f} s, "
        f"{1e3 * sum(steady) / max(len(steady), 1):.1f} ms/iter, "
        f"K={r.k}" + ("" if nmi is None else f", NMI={nmi:.4f}")
        + f", peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
        f"({mem.get('peak_bytes_source')}), sweep_paths={r.sweep_paths}, "
        f"K over the last 10 iters {[int(k) for k in r.history['k'][-10:]]}")


def fit(cfg, x, mesh, **kw):
    from repro.core.sampler import DPMM
    t0 = time.perf_counter()
    r = DPMM(cfg, mesh=mesh).fit(x, **kw)
    return r, time.perf_counter() - t0


def check_fit(name, r, gt, wall, pallas: bool, programs=None) -> None:
    """Print the fit's smoke figures, then hold it to the thresholds."""
    nmi = r.nmi(gt)
    report(name, r, wall, nmi)
    check(nmi >= NMI_MIN, f"{name}: NMI {nmi:.4f} < {NMI_MIN}")
    check(K_WINDOW[0] <= r.k <= K_WINDOW[1],
          f"{name}: K={r.k} outside {K_WINDOW}")
    purity = agreement(r.labels, gt)
    check(purity >= PURITY_MIN, f"{name}: purity {purity:.4f} < "
          f"{PURITY_MIN}: a found cluster mixes true clusters")
    check(covers(r.labels, gt), f"{name}: a true cluster was lost")
    want = "sweep_fast" if pallas else "sweep_ref"
    check(set(r.sweep_paths) == {want},
          f"{name}: sweep bodies {r.sweep_paths}, expected only {want}")
    if programs is not None:
        check(bool(programs) and all("tpu_custom_call" in t
                                     for t in programs),
              f"{name}: a compiled chunk has no Mosaic kernel")


def gauss_cfg(**kw):
    from repro.configs import DPMMConfig
    return DPMMConfig(alpha=10.0, iters=ITERS, k_max=64, burnout=5,
                      log_every=10, **kw)


def phase_a(mesh, seed):
    from repro.data.synthetic import generate_gmm
    x, gt = generate_gmm(GAUSS["n"], GAUSS["d"], K_TRUE, seed=seed)
    ref, wall = fit(gauss_cfg(seed=seed), x, mesh)
    check_fit("(a) gaussian jnp reference", ref, gt, wall, pallas=False)
    with compiled_programs() as programs:
        pal, wall = fit(gauss_cfg(seed=seed, use_pallas=True), x, mesh)
    check_fit("(a) gaussian pallas", pal, gt, wall, pallas=True,
              programs=programs)
    share = agreement(pal.labels, ref.labels)
    say(f"(a) pallas vs jnp label agreement {share:.6f}")
    check(share >= AGREE_MIN,
          f"(a) pallas/jnp label agreement {share:.6f} < {AGREE_MIN}")
    return x, gt, pal


def phase_b(mesh, seed):
    from repro.configs import DPMMConfig
    from repro.data.synthetic import generate_mnmm
    x, gt = generate_mnmm(MULT["n"], MULT["d"], K_TRUE, seed=seed)
    cfg = DPMMConfig(component="multinomial", alpha=10.0, iters=ITERS,
                     k_max=64, burnout=5, log_every=10, use_pallas=True,
                     seed=seed)
    with compiled_programs() as programs:
        r, wall = fit(cfg, x, mesh)
    check_fit("(b) multinomial pallas", r, gt, wall, pallas=True,
              programs=programs)


def phase_c(mesh, seed, x, gt, fitted):
    """(a)'s chain continued for a few iterations on the streamed plane;
    it is held to the same recovery checks as (a)."""
    iters = 3
    r, wall = fit(gauss_cfg(seed=seed, use_pallas=True, tile_size=TILE),
                  x, mesh, iters=iters, init_state=fitted.state)
    check(r.device_bytes.get("mode") == "tiled", "(c) did not stream")
    check_fit(f"(c) tiled pallas, {iters} iters over "
              f"{-(-x.shape[0] // TILE)} tiles", r, gt, wall, pallas=True)
    say(f"(c) tiled vs resident label agreement "
        f"{float((r.labels == fitted.labels).mean()):.6f}")


def phase_d(x, gt, fitted):
    import numpy as np
    from repro.core.checkpoint import save_model
    from repro.serve.dpmm import DPMMEngine, ServeConfig
    with tempfile.TemporaryDirectory() as tmp:
        path = save_model(os.path.join(tmp, "model.npz"), fitted.state,
                          "gaussian")
        t0 = time.perf_counter()
        engine = DPMMEngine.from_checkpoint(path,
                                            ServeConfig(use_pallas=True))
        up = time.perf_counter() - t0
    say(f"[smoke figure, not a benchmark] (d) engine up in {up:.1f} s, "
        f"ladder={engine.batch_sizes}")
    off = 0
    for rows in LADDER_REQUESTS:
        q = x[off:off + rows]
        t0 = time.perf_counter()
        res = engine.query(q)
        dt = time.perf_counter() - t0
        share = float((res.labels == fitted.labels[off:off + rows]).mean())
        say(f"[smoke figure, not a benchmark] (d) {rows}-row request "
            f"{1e3 * dt:.2f} ms, label agreement with the fit {share:.6f}")
        check(res.labels.shape == (rows,)
              and np.isfinite(res.log_predictive).all(),
              f"(d) {rows}-row answer malformed")
        check(share >= SERVE_AGREE_MIN,
              f"(d) served labels agree with the fit on {share:.6f}")
        purity = agreement(res.labels, gt[off:off + rows])
        check(purity >= PURITY_MIN,
              f"(d) served labels' purity {purity:.4f} < {PURITY_MIN}")
        off += rows
    q = x[:LADDER_REQUESTS[-1]]
    drawn = engine.sample(q, seed=1)
    hard = engine.query(q).labels
    share = float((drawn == hard).mean())
    say(f"(d) sampled vs argmax label agreement {share:.6f}")
    check(set(np.unique(drawn)) <= set(engine.slots.tolist()),
          "(d) sampled labels outside the active slots")
    check(share >= SERVE_AGREE_MIN,
          f"(d) sampled labels agree with argmax on {share:.6f}")


def four_chips(seed):
    """Phase (a)'s Pallas fit data-sharded over 4 chips vs the same fit on
    one chip. The 4-chip fit psums per-chip stat partials, so its sums
    associate differently from the 1-chip fold and the two chains need
    not be bitwise equal; the partition, K and K-history must agree."""
    import jax
    import numpy as np
    from repro.core.distributed import make_data_mesh
    from repro.data.synthetic import generate_gmm
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    x, gt = generate_gmm(GAUSS["n"], GAUSS["d"], K_TRUE, seed=seed)
    fits = {}
    for n_dev in (4, 1):
        r, wall = fit(gauss_cfg(seed=seed, use_pallas=True), x,
                      make_data_mesh(n_dev))
        check_fit(f"{n_dev}-chip gaussian pallas", r, gt, wall, pallas=True)
        check(r.device_bytes.get("mesh_devices") == n_dev,
              f"{n_dev}-chip fit ran on {r.device_bytes} devices")
        fits[n_dev] = r
    four, one = fits[4], fits[1]
    exact = bool(np.array_equal(four.labels, one.labels))
    share = agreement(four.labels, one.labels)
    hist = float(np.mean(four.history["k"] == one.history["k"]))
    say(f"4 vs 1 chip: labels bitwise equal={exact}, label agreement "
        f"{share:.6f} (differing share {1 - share:.6f}), K {four.k} vs "
        f"{one.k}, K-history agreement {hist:.4f}")
    check(share >= AGREE_MIN, f"4/1-chip label agreement {share:.6f}")
    check(four.k == one.k, f"4/1-chip K differ: {four.k} vs {one.k}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-sharded fit and the "
                         "1-chip fit it is compared with")
    args = ap.parse_args(argv)
    try:
        from repro.launch.entry import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    configure_compile_cache(ROOT)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              "(Pallas would run in interpret mode)", file=sys.stderr)
        return 1
    say(f"device: {dev.device_kind} x{len(jax.devices())}")
    try:
        if args.four_chips:
            four_chips(args.seed)
        else:
            from repro.core.distributed import make_data_mesh
            one = make_data_mesh(1)
            x, gt, fitted = phase_a(one, args.seed)
            phase_c(one, args.seed, x, gt, fitted)
            phase_d(x, gt, fitted)
            del x, gt, fitted
            phase_b(one, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
