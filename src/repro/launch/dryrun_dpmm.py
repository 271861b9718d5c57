"""Production-mesh dry-run for the paper's OWN workload: one distributed
DPMM iteration (restricted Gibbs + split/merge) over N points sharded
across 256 / 512 chips.

    PYTHONPATH=src python -m repro.launch.dryrun_dpmm [--n 1000000] [--d 64]
        [--multi-pod] [--shard-features]

Verifies structurally (C3): every collective is O(K_max * T) suff-stats /
scalars — the O(N d / chips) point shard never crosses the wire — and
reports the three roofline terms for the sweep.
"""
# placeholder devices BEFORE any jax import (see dryrun.py)
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import functools
import json

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import DPMMConfig
from repro.core.family import get_family, state_partition_specs
from repro.core.sampler import dpmm_step
from repro.core.state import ModelState, PointState
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.roofline.analysis import analyze, save_json

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--k-max", type=int, default=64)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--shard-features", action="store_true",
                    help="shard d over 'model' (multinomial component "
                         "only: the Gaussian full-covariance Mahalanobis "
                         "is not feature-separable — DESIGN §10)")
    ap.add_argument("--out-dir", default=OUT_DIR)
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_name = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    chips = mesh_chips(mesh)
    axes = tuple(a for a in mesh.axis_names if a != "model")
    n_data_shards = 1
    for a in axes:
        n_data_shards *= mesh.shape[a]
    n_local = -(-args.n // n_data_shards)
    n = n_local * n_data_shards

    # --shard-features => multinomial family (the paper's 20newsgroups
    # d=20,000 regime; Gaussian full-covariance is not feature-separable)
    family = get_family("multinomial" if args.shard_features else "gaussian")
    feat_axis = "model" if args.shard_features else None
    cfg = DPMMConfig(alpha=10.0, k_max=args.k_max, burnout=0,
                     component=family.name,
                     shard_features=args.shard_features)
    prior = family.build_prior(cfg, jnp.zeros((1, args.d), jnp.float32))
    kwargs = dict(prior=prior, family=family, cfg=cfg, axes=axes,
                  k_max=cfg.k_max, feat_axis=feat_axis)

    shard_spec = P(axes)
    x_spec = P(axes, feat_axis)
    state_specs = state_partition_specs(family, shard_spec)

    # abstract state/input (ShapeDtypeStruct only — no allocation): the
    # family's own empty_stats/expected_params give the per-family shapes
    k = args.k_max
    d = args.d
    f32 = jnp.float32
    stats_s = jax.eval_shape(lambda: family.empty_stats((k,), d))
    substats_s = jax.eval_shape(lambda: family.empty_stats((k, 2), d))
    params_s = jax.eval_shape(family.expected_params, prior, stats_s)
    subparams_s = jax.eval_shape(family.expected_params, prior, substats_s)
    model = ModelState(
        key=jax.eval_shape(lambda: jax.random.key(0)),
        it=jax.ShapeDtypeStruct((), jnp.int32),
        active=jax.ShapeDtypeStruct((k,), bool),
        logweights=jax.ShapeDtypeStruct((k,), f32),
        sub_logweights=jax.ShapeDtypeStruct((k, 2), f32),
        stuck=jax.ShapeDtypeStruct((k,), jnp.int32),
        params=params_s,
        subparams=subparams_s,
        stats=stats_s,
        substats=substats_s)
    point = PointState(
        labels=jax.ShapeDtypeStruct((n,), jnp.int32),
        sublabels=jax.ShapeDtypeStruct((n,), jnp.int32),
        valid=jax.ShapeDtypeStruct((n,), f32))
    xs = jax.ShapeDtypeStruct((n, d), f32)

    step = jax.jit(jax.shard_map(
        functools.partial(dpmm_step, **kwargs), mesh=mesh,
        in_specs=(*state_specs, x_spec),
        out_specs=state_specs, check_vma=False))
    with mesh:
        lowered = step.lower(model, point, xs)
        compiled = lowered.compile()

    # MODEL_FLOPS: the O(N K T) loglik/suffstat passes (T = d^2 Gaussian,
    # T = d multinomial — paper §4.4) + the O(K^2 d^3) all-pairs merge
    # marginals for Gaussian (they dominate when N/chips < K*d)
    gaussian = family.name == "gaussian"
    t_term = d * d if gaussian else d
    model_flops = (8.0 * n * args.k_max * t_term / chips
                   + (args.k_max ** 2 / 2 * d ** 3 / 3 if gaussian
                      else 0.0))
    r = analyze(compiled,
                arch=f"dpmm-{family.name}",
                shape=f"N{args.n}_d{d}_K{args.k_max}"
                      + ("_featshard" if args.shard_features else ""),
                mesh_name=mesh_name, chips=chips, model_flops=model_flops)
    mem = compiled.memory_analysis()
    print(f"--- DPMM N={n} d={d} K_max={args.k_max} on {mesh_name} "
          f"({'feature-sharded' if args.shard_features else 'replicated-d'})")
    print(f"    memory: args={r.mem_args/2**30:.2f}GiB "
          f"temp={r.mem_temp/2**30:.2f}GiB")
    print(f"    flops/dev={r.flops_per_device:.3e} "
          f"bytes/dev={r.bytes_per_device:.3e}")
    print(f"    collectives: " + ", ".join(
        f"{kk}={v/2**20:.2f}MiB" for kk, v in r.coll_bytes.items() if v))
    print(f"    roofline: compute={r.t_compute*1e3:.3f}ms "
          f"memory={r.t_memory*1e3:.3f}ms "
          f"collective={r.t_collective*1e3:.3f}ms -> {r.bottleneck}-bound, "
          f"useful={r.useful_ratio:.3f}")
    # C3 structural check: total collective volume must be O(K d^2), not O(N d)
    suffstat_bytes = args.k_max * (1 + d + d * d) * 4 * 3 * 2 * 10
    shard_bytes = n // n_data_shards * d * 4
    total_coll = r.collective_total
    verdict = ("OK (<< shard)" if total_coll < shard_bytes else
               "suff-stats exceed the shard (high-d regime: K*d^2 > "
               "N_local*d; no point data moves — see EXPERIMENTS)")
    print(f"    C3 check: collective/step = {total_coll/2**20:.2f} MiB; "
          f"point shard = {shard_bytes/2**20:.2f} MiB; {verdict}")
    save_json(r, os.path.join(
        args.out_dir, f"dpmm__{r.shape}__{mesh_name}.json"))


if __name__ == "__main__":
    main()
