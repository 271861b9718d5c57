"""The served model of a full-covariance Gaussian configuration, made
from the seed: the mixture that ``chipbench.datagen.generate_gmm`` draws
its points from, placed on k of the configuration's k_max slots. It is
kept twice: as the reference takes it (float32 means and precision
factors, float64 log weights and log determinants) and as the program's
engine takes it (a dense ``ModelState``, the same float32 numbers)."""
from __future__ import annotations

import numpy as np

from chipbench import datagen


def build(config: dict, seed: int, ref) -> dict:
    data, k_max = config["data"], int(config["dpmm"]["k_max"])
    d, k = int(data["d"]), int(data["k"])
    mix = datagen.gmm_mixture(d, k, seed, float(data["sep"]))
    params = ref.mixture_params(mix["means"], mix["covs"])
    slots_rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    slots = np.sort(slots_rng.permutation(k_max)[:k]).astype(np.int32)
    served = {"params": params, "logdet": ref.log_dets(params),
              "logw": np.log(mix["weights"]), "slots": slots,
              "k_max": k_max, "d": d}
    served["state"] = program_state(served, mix, float(data["n"]))
    return served


def program_state(served: dict, mix: dict, n_fit: float):
    """The dense ``ModelState`` the engine serves: active slots hold the
    mixture, every other slot is inactive. Statistics are those of
    ``n_fit`` points at the mixture's weights (the engine reads only
    their shape; a swap's health check reads them too)."""
    import jax
    import jax.numpy as jnp
    from repro.core.niw import GaussParams, GaussStats
    from repro.core.state import ModelState

    k_max, d, slots = served["k_max"], served["d"], served["slots"]
    f32 = np.float32
    mu = np.zeros((k_max, d), f32)
    factor = np.tile(np.eye(d, dtype=f32), (k_max, 1, 1))
    logdet = np.zeros((k_max,), f32)
    logw = np.full((k_max,), -1e30, f32)
    active = np.zeros((k_max,), bool)
    mu[slots] = served["params"]["mu"]
    factor[slots] = served["params"]["factor"]
    logdet[slots] = served["logdet"]
    logw[slots] = served["logw"]
    active[slots] = True
    n = np.zeros((k_max,), np.float64)
    n[slots] = n_fit * mix["weights"]
    means = np.zeros((k_max, d))
    covs = np.tile(np.eye(d), (k_max, 1, 1))
    means[slots], covs[slots] = mix["means"], mix["covs"]
    sx = n[:, None] * means
    sxx = n[:, None, None] * (covs + means[:, :, None] * means[:, None, :])
    stats = GaussStats(n=jnp.asarray(n, f32), sx=jnp.asarray(sx, f32),
                       sxx=jnp.asarray(sxx, f32))
    params = GaussParams(mu=jnp.asarray(mu), chol_prec=jnp.asarray(factor),
                         logdet_prec=jnp.asarray(logdet))
    two = lambda a: jnp.repeat(a[:, None], 2, axis=1)
    return ModelState(
        key=jax.random.key(0), it=jnp.zeros((), jnp.int32),
        active=jnp.asarray(active), logweights=jnp.asarray(logw),
        sub_logweights=jnp.full((k_max, 2), np.log(0.5), f32),
        stuck=jnp.zeros((k_max,), jnp.int32), params=params,
        subparams=jax.tree.map(two, params), stats=stats,
        substats=jax.tree.map(lambda a: two(a) * 0.5, stats))
