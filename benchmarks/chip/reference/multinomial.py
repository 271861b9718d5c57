"""Plain reference of the multinomial (Dirichlet) DPMM's per-iteration
mathematics, for the benchmark's ``correct``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision
(or in bfloat16, for the control). It imports nothing of the program:
``read_params`` and ``read_stats`` only read the fields of the state the
program returned.

Model: x | k ~ Multinomial(theta_k) over d words. The multinomial
coefficient is the same under every cluster and cancels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FIELDS = ("n", "counts")


def read_params(params) -> dict:
    return {"logtheta": np.asarray(params.logtheta, np.float32)}


def read_stats(stats) -> dict:
    return {f: np.asarray(getattr(stats, f), np.float32) for f in FIELDS}


def round_params(params: dict, dtype) -> dict:
    """The parameters as the control holds them: stored in ``dtype``."""
    return {k: np.asarray(jnp.asarray(v).astype(dtype).astype(jnp.float32))
            for k, v in params.items()}


def fold(x: jax.Array, seg: jax.Array, n_seg: int, dtype) -> dict:
    """Statistics (n, summed counts) of the points of each segment."""
    onehot = jax.nn.one_hot(seg, n_seg, dtype=dtype)
    return {"n": jnp.sum(onehot, axis=0, dtype=dtype),
            "counts": jnp.einsum("ns,nf->sf", onehot, x.astype(dtype),
                                 precision=HIGHEST,
                                 preferred_element_type=dtype)}


def log_dets(params: dict) -> np.ndarray:
    """No per-slot normalizer beyond log theta: zeros."""
    return np.zeros(params["logtheta"].shape[:-1])


def logp(x: jax.Array, logw: jax.Array, params: dict,
         logdet: jax.Array) -> jax.Array:
    """(B, K) log weight plus log likelihood of each point under each
    slot, float32 at highest precision."""
    ll = jnp.einsum("bd,kd->bk", x, params["logtheta"], precision=HIGHEST)
    return logw[None, :] + ll + logdet[None, :]
