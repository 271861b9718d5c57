"""DPMM sampling driver — the paper's §3.4 command-line entry point.

    PYTHONPATH=src python -m repro.launch.sample_dpmm \
        --n 100000 --d 2 --k 10 --alpha 10 --iters 100 [--prior-type \
        Multinomial] [--params-path params.json] [--result-path out.json]

Mirrors the reference CLI: ``--params_path`` JSON overrides hyperparams
(alpha, k_max, burnout, ...); the result JSON carries predicted labels,
weights, NMI and per-iteration running times — the same fields the paper's
result file documents (§3.4.3).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

from repro.configs import DPMMConfig
from repro.core.family import available_families
from repro.core.sampler import DPMM
from repro.data.synthetic import generate_gmm, generate_mnmm, generate_pmm
from repro.launch.entry import configure_compile_cache, require_chip_for_pallas

# reference-CLI aliases on top of the registry's canonical names
_PRIOR_ALIASES = {"gaussian": "gaussian", "multinomial": "multinomial",
                  "poisson": "poisson", "diaggaussian": "diag_gaussian"}


def _component_of(prior_type: str) -> str:
    name = prior_type.lower()
    name = _PRIOR_ALIASES.get(name, name)
    if name not in available_families():
        raise SystemExit(
            f"unknown --prior-type {prior_type!r}; known: "
            f"{', '.join(available_families())} (or reference-CLI aliases "
            f"{', '.join(sorted(_PRIOR_ALIASES))})")
    return name


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--d", type=int, default=2)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--alpha", type=float, default=10.0)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prior-type", "--prior_type", default="Gaussian",
                    help="component family: any registry name "
                         "(gaussian, diag_gaussian, multinomial, poisson) "
                         "or the reference CLI's capitalized aliases")
    ap.add_argument("--data-path", default="", help=".npy (N, d) input; "
                    "with --tile-size it is memory-mapped, never fully "
                    "loaded (out-of-core)")
    ap.add_argument("--params-path", "--params_path", default="")
    ap.add_argument("--result-path", "--result_path", default="")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--tile-size", "--tile_size", type=int, default=None,
                    help="stream points through tiles of this many rows "
                         "per shard (out-of-core data plane; device memory "
                         "becomes O(k_max + tile_size)). Default: resident")
    ap.add_argument("--n-chains", "--n_chains", type=int, default=1,
                    help="parallel MCMC chains sharing one device copy of "
                         "x; the result (and checkpoint) is the best-"
                         "scoring chain, with split-R-hat printed")
    ap.add_argument("--checkpoint-path", "--checkpoint_path", default="",
                    help="write the fitted ModelState npz here "
                         "(core/checkpoint.py; servable via "
                         "repro.launch.serve_dpmm). With "
                         "--checkpoint-every it is the auto-checkpoint "
                         "rotation prefix instead")
    ap.add_argument("--checkpoint-every", "--checkpoint_every", type=int,
                    default=None,
                    help="auto-checkpoint the fit every this many "
                         "iterations to the --checkpoint-path rotation "
                         "(atomic, CRC-verified, last-"
                         "`DPMMConfig.checkpoint_keep` members kept)")
    ap.add_argument("--resume", action="store_true",
                    help="resume a killed fit from the newest VERIFYING "
                         "member of the --checkpoint-path rotation; "
                         "--iters is the total target, so only the "
                         "remaining iterations run. No checkpoint yet "
                         "means a fresh fit — rerunning the same "
                         "command until it finishes is safe")
    ap.add_argument("--workers", type=int, default=None,
                    help="elastic multi-process sampling: spawn this many "
                         "worker shard processes (repro.dist), each "
                         "streaming a row range of x; the chain is "
                         "bitwise identical to the single-process fit at "
                         "any worker count, and SIGKILL'd/hung workers "
                         "fail over to survivors. Composes with "
                         "--tile-size/--checkpoint-every/--resume")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    configure_compile_cache()

    overrides = {}
    if args.params_path:
        with open(args.params_path) as f:
            overrides = json.load(f)
    cfg = DPMMConfig(
        component=_component_of(args.prior_type),
        alpha=overrides.get("alpha", args.alpha),
        iters=overrides.get("iters", args.iters),
        k_max=overrides.get("k_max", 64),
        burnout=overrides.get("burnout", 15),
        log_every=overrides.get("log_every", 10),
        use_pallas=args.use_pallas or overrides.get("use_pallas", False),
        tile_size=(args.tile_size if args.tile_size is not None
                   else overrides.get("tile_size")),
        checkpoint_path=(args.checkpoint_path or None),
        checkpoint_every=args.checkpoint_every,
        workers=(args.workers if args.workers is not None
                 else overrides.get("workers")),
        seed=args.seed,
    )
    require_chip_for_pallas(cfg.use_pallas)
    if (args.resume or args.checkpoint_every) and not args.checkpoint_path:
        raise SystemExit("--resume/--checkpoint-every need "
                         "--checkpoint-path (the rotation prefix)")

    if args.data_path:
        if cfg.tile_size is not None:
            from repro.data.source import HostTiledSource
            x = HostTiledSource.from_npy(args.data_path)
        else:
            x = np.load(args.data_path)
        gt = None
    elif cfg.component in ("gaussian", "diag_gaussian"):
        x, gt = generate_gmm(args.n, args.d, args.k, seed=args.seed)
    elif cfg.component == "poisson":
        x, gt = generate_pmm(args.n, args.d, args.k, seed=args.seed)
    else:
        x, gt = generate_mnmm(args.n, args.d, args.k, seed=args.seed)

    from repro.data.source import as_source
    source = as_source(x)
    print(f"DPMM fit: N={source.n} d={source.d} component="
          f"{cfg.component} alpha={cfg.alpha} iters={cfg.iters} "
          f"tile_size={cfg.tile_size}"
          + (f" workers={cfg.workers}" if cfg.workers else ""))
    t0 = time.time()
    model = DPMM(cfg)
    result = model.fit(source, verbose=args.verbose,
                       n_chains=args.n_chains, resume=args.resume)
    wall = time.time() - t0
    if result.recoveries:
        kinds = sorted({e["kind"] for e in result.recoveries})
        print(f"recovered from {len(result.recoveries)} fault event(s) "
              f"({', '.join(kinds)}) — see FitResult.recoveries")
    if result.n_chains > 1:
        try:
            rhats = {k: round(v, 3) for k, v in result.rhats().items()}
        except ValueError:          # too few iterations for split-R-hat
            rhats = "n/a (needs >= 4 iters)"
        print(f"chains: scores={np.round(np.asarray(result.score), 2)} "
              f"rhat={rhats}")
        result = result.select_best()
    nmi = result.nmi(gt) if gt is not None else float("nan")
    print(f"done in {wall:.1f}s: K={result.k} NMI={nmi:.4f} "
          f"mean iter {np.mean(result.iter_times_s[1:])*1e3:.1f} ms")
    if args.checkpoint_path and not args.checkpoint_every:
        from repro.core.checkpoint import save_model
        path = save_model(args.checkpoint_path, result.state,
                          cfg.component)
        print(f"wrote checkpoint {path}")
    elif args.checkpoint_every:
        # the fit already wrote the final rotation member (atomic,
        # CRC-verified); point the operator at it
        from repro.core.checkpoint import list_checkpoints
        members = list_checkpoints(cfg.checkpoint_path)
        if members:
            print(f"final checkpoint {members[0][1]}")
    mem = result.device_bytes or {}
    print(f"device memory [{mem.get('mode')}]: "
          f"est_peak={mem.get('est_peak_bytes', 0)/2**20:.2f} MiB"
          + (f"  measured_peak={mem['peak_bytes_in_use']/2**20:.2f} MiB"
             if mem.get("peak_bytes_in_use") else ""))

    if args.result_path:
        weights = np.exp(np.asarray(result.state.logweights))
        active = np.asarray(result.state.active)
        out = {
            "labels": result.labels.tolist(),
            "weights": weights[active].tolist(),
            "k": result.k,
            "nmi": nmi,
            "iter_times_s": result.iter_times_s,
            "device_bytes": result.device_bytes,
            "config": dataclasses.asdict(cfg),
            # distributed fits: per-worker shard ranges + failover
            # tallies, and the full recovery event log
            "dist": result.dist,
            "recoveries": result.recoveries,
        }
        with open(args.result_path, "w") as f:
            json.dump(out, f)
        print(f"wrote {args.result_path}")


if __name__ == "__main__":
    main()
