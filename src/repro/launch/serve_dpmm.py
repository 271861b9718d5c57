"""DPMM serving driver — query a fitted model from the command line.

    # 1. fit + checkpoint (sample_dpmm writes the npz):
    PYTHONPATH=src python -m repro.launch.sample_dpmm \
        --n 100000 --d 8 --k 10 --iters 100 --n-chains 4 \
        --checkpoint-path model.npz
    # 2. serve queries against it:
    PYTHONPATH=src python -m repro.launch.serve_dpmm \
        --checkpoint model.npz --queries q.npy --result-path out.json

``--checkpoint`` accepts a single npz OR an auto-checkpoint rotation
prefix (the newest verifying member serves). ``--batch-sizes`` is the
AOT ladder — every size precompiles at startup and each request routes
to the smallest covering step (serve/dpmm.py).

The JSON written to ``--result-path`` is exactly
``ServeResult.to_json()`` — the CLI and the Python API emit the same
schema, field for field. With ``--profile-dir DIR`` the query runs
under ``jax.profiler.trace(DIR)``: the trace holds the engine's
``dpmm.serve.*`` spans (serve/dpmm.py) beside the device's operations,
for TensorBoard or Perfetto. Without ``--queries`` a synthetic batch
matching the checkpoint's feature dim is drawn — a smoke mode for CI
and demos.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time
import warnings

import numpy as np


def _parse_sizes(text: str):
    try:
        return tuple(int(tok) for tok in text.split(",") if tok.strip())
    except ValueError:
        raise SystemExit(f"--batch-sizes expects comma-separated ints, "
                         f"got {text!r}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--checkpoint", required=True,
                    help="ModelState npz (or rotation prefix) written by "
                         "core/checkpoint.py")
    ap.add_argument("--queries", default="",
                    help=".npy (N, d) query rows; default: synthetic")
    ap.add_argument("--n", type=int, default=10_000,
                    help="synthetic query count when --queries is unset")
    ap.add_argument("--batch-sizes", "--batch_sizes", default="",
                    help="comma-separated ascending AOT ladder, e.g. "
                         "256,2048,8192 (ServeConfig default when unset)")
    ap.add_argument("--batch-size", "--batch_size", type=int, default=None,
                    help="DEPRECATED: single AOT size; use --batch-sizes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--sample", action="store_true",
                    help="also draw a sampled (Gumbel) assignment per row")
    ap.add_argument("--include-logprobs", action="store_true",
                    help="include the (N, K_max) soft assignment in the "
                         "result JSON")
    ap.add_argument("--result-path", "--result_path", default="")
    ap.add_argument("--profile-dir", default="",
                    help="write a profiler trace of the query here")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.entry import (configure_compile_cache,
                                    require_chip_for_pallas)
    from repro.serve.dpmm import DPMMEngine, ServeConfig
    configure_compile_cache()
    require_chip_for_pallas(args.use_pallas)

    fields = {"use_pallas": args.use_pallas, "seed": args.seed}
    if args.batch_size is not None:
        if args.batch_sizes:
            raise SystemExit("pass --batch-sizes OR --batch-size, not both")
        warnings.warn("--batch-size is deprecated; use --batch-sizes",
                      DeprecationWarning)
        fields["batch_sizes"] = (args.batch_size,)
    elif args.batch_sizes:
        fields["batch_sizes"] = _parse_sizes(args.batch_sizes)
    cfg = ServeConfig(**fields)

    t0 = time.time()
    engine = DPMMEngine.from_checkpoint(args.checkpoint, cfg)
    print(f"engine up in {time.time() - t0:.2f}s: "
          f"family={engine.family.name} d={engine.d} k_max={engine.k_max} "
          f"ladder={engine.batch_sizes} (all steps precompiled)")

    if args.queries:
        xq = np.asarray(np.load(args.queries), np.float32)
    else:
        rng = np.random.default_rng(args.seed)
        xq = rng.standard_normal((args.n, engine.d)).astype(np.float32)
        print(f"no --queries: serving {args.n} synthetic rows")

    profile = (jax.profiler.trace(args.profile_dir) if args.profile_dir
               else contextlib.nullcontext())
    with profile:
        t0 = time.perf_counter()
        res = engine.query(xq, sample=args.sample, seed=args.seed)
        dt = time.perf_counter() - t0
    print(f"served {xq.shape[0]} queries in {dt * 1e3:.1f} ms "
          f"({xq.shape[0] / dt:,.0f} q/s): "
          f"{len(res.cluster_counts())} clusters hit, "
          f"mean log p(x) = {res.log_predictive.mean():.3f}")
    if args.profile_dir:
        print(f"wrote a profiler trace under {args.profile_dir}")
    if args.result_path:
        with open(args.result_path, "w") as f:
            json.dump(res.to_json(include_logprobs=args.include_logprobs),
                      f)
        print(f"wrote {args.result_path}")


if __name__ == "__main__":
    main()
