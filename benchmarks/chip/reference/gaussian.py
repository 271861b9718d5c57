"""Plain reference of the full-covariance Gaussian DPMM's mathematics,
for the benchmark's ``correct``: the statistics a fit's labels fold to,
and the log weight plus log density of points under clusters (a fit's
label draws, a served model's answers).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision
(or in bfloat16, for the control) plus float64 NumPy for log
determinants. It imports nothing of the program: ``read_params`` and
``read_stats`` only read the fields of the state the program returned.

Model (Chang & Fisher III 2013; the paper, eq. 9): x | k ~ N(mu_k,
Sigma_k). A cluster's precision is kept as a factor F with Sigma^-1 =
F F^T, as the program keeps it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LOG_2PI = math.log(2.0 * math.pi)
FIELDS = ("n", "sx", "sxx")


def read_params(params) -> dict:
    return {"mu": np.asarray(params.mu, np.float32),
            "factor": np.asarray(params.chol_prec, np.float32)}


def read_stats(stats) -> dict:
    return {f: np.asarray(getattr(stats, f), np.float32) for f in FIELDS}


def round_params(params: dict, dtype) -> dict:
    """The parameters as the control holds them: stored in ``dtype``."""
    return {k: np.asarray(jnp.asarray(v).astype(dtype).astype(jnp.float32))
            for k, v in params.items()}


def fold(x: jax.Array, seg: jax.Array, n_seg: int, dtype) -> dict:
    """Statistics (n, sum x, sum x x^T) of the points of each segment,
    in ``dtype`` (float32 at highest precision, or the control's
    bfloat16)."""
    onehot = jax.nn.one_hot(seg, n_seg, dtype=dtype)
    xd = x.astype(dtype)
    outer = (xd[:, :, None] * xd[:, None, :]).reshape(x.shape[0], -1)
    dot = lambda a, b: jnp.einsum("ns,nf->sf", a, b, precision=HIGHEST,
                                  preferred_element_type=dtype)
    d = x.shape[1]
    return {"n": jnp.sum(onehot, axis=0, dtype=dtype),
            "sx": dot(onehot, xd),
            "sxx": dot(onehot, outer).reshape(n_seg, d, d)}


def log_dets(params: dict) -> np.ndarray:
    """log det Sigma^-1 = 2 log |det F| for every slot, in float64."""
    _, logabs = np.linalg.slogdet(params["factor"].astype(np.float64))
    return 2.0 * logabs


def logp(x: jax.Array, logw: jax.Array, params: dict,
         logdet: jax.Array) -> jax.Array:
    """(B, K) log weight plus log density of each point under each slot,
    float32 at highest precision."""
    diff = x[:, None, :] - params["mu"][None, :, :]
    y = jnp.einsum("bkd,kde->bke", diff, params["factor"],
                   precision=HIGHEST)
    maha = jnp.sum(y * y, axis=-1)
    d = x.shape[1]
    return logw[None, :] + 0.5 * (logdet[None, :] - maha) - 0.5 * d * LOG_2PI


def logp_in(x: jax.Array, logw: jax.Array, params: dict, logdet: jax.Array,
            dtype) -> jax.Array:
    """``logp`` with every operand and every operation in ``dtype`` (the
    control's bfloat16), returned as float32."""
    c = lambda a: jnp.asarray(a).astype(dtype)
    diff = c(x)[:, None, :] - c(params["mu"])[None, :, :]
    y = jnp.einsum("bkd,kde->bke", diff, c(params["factor"]),
                   preferred_element_type=dtype)
    maha = jnp.sum(y * y, axis=-1, dtype=dtype)
    d = x.shape[1]
    out = (c(logw)[None, :] + c(0.5) * (c(logdet)[None, :] - maha)
           - c(0.5 * d * LOG_2PI))
    return out.astype(jnp.float32)


def mixture_params(means: np.ndarray, covs: np.ndarray) -> dict:
    """A mixture's (mu, factor) as this reference keeps them: float32
    means and the lower Cholesky factor F of each precision, Sigma^-1 =
    F F^T, worked out in float64."""
    return {"mu": means.astype(np.float32),
            "factor": np.linalg.cholesky(np.linalg.inv(covs))
            .astype(np.float32)}
