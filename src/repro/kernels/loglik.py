"""Pallas TPU kernel for the Gaussian log-likelihood matrix (N, K) — the
paper's `dcolwise_dot_all_kernel` + per-stream likelihood hot spot (§4.1e).

For each (point-tile, cluster-tile): diff = x - mu (bn, bk, d) broadcast in
VMEM, whitening y = diff @ F_k on the MXU (batched over the bk clusters),
row-reduce ||y||^2 on the VPU. O(N K d^2) FLOPs — the dominant term of the
paper's complexity O(N K T / G) with T = d^2.

Tiling: grid (N/bn, K/bk); VMEM per step =
    x (bn, d) + mu/F (bk d + bk d^2) + diff/y (2 bn bk d) + out (bn, bk)
with bn=128, bk=8, d<=128 that is ~1.6 MiB — well inside the ~16 MiB VMEM.
``MAX_KERNEL_D`` makes the d<=128 assumption explicit: the per-step VMEM
footprint grows as bk*d^2 + 2*bn*bk*d, so beyond 128 the tile no longer
fits the budget and ``loglik`` falls back to the jnp reference
(kernels/ref.py) instead of silently blowing VMEM at Mosaic compile time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.assign import _cols, col_spec, gauss_block_ll
from repro.kernels.suffstats import MAX_KERNEL_D  # shared VMEM ceiling


def _loglik_kernel(x_ref, mu_ref, f_ref, ld_ref, o_ref):
    d = x_ref.shape[-1]
    o_ref[...] = gauss_block_ll(x_ref[...], mu_ref[...], f_ref[...],
                                ld_ref[...], d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def loglik(x: jax.Array, mu: jax.Array, chol_prec: jax.Array,
           logdet_prec: jax.Array, *, bn: int = 128, bk: int = 8,
           interpret: bool = False) -> jax.Array:
    """x: (N, d); mu: (K, d); chol_prec: (K, d, d); logdet: (K,) -> (N, K)."""
    n, d = x.shape
    if d > MAX_KERNEL_D:                 # documented VMEM guard: jnp path
        return ref.loglik(x, mu, chol_prec, logdet_prec)
    k = mu.shape[0]
    bn = min(bn, n) or 1
    bk = min(bk, k) or 1
    pn, pk = (-n) % bn, (-k) % bk
    if pn:
        x = jnp.pad(x, ((0, pn), (0, 0)))
    if pk:
        mu = jnp.pad(mu, ((0, pk), (0, 0)))
        eye = jnp.broadcast_to(jnp.eye(d, dtype=chol_prec.dtype),
                               (pk, d, d))
        chol_prec = jnp.concatenate([chol_prec, eye], axis=0)
        logdet_prec = jnp.pad(logdet_prec, (0, pk))
    gn, gk = x.shape[0] // bn, mu.shape[0] // bk

    # the output leaves as (K/bk, N, bk) tiles — whole last dims, so any bk
    # is a legal block — and is laid back out to (N, K) by XLA
    out = pl.pallas_call(
        _loglik_kernel,
        grid=(gn, gk),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, 1, d), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bk, d, d), lambda i, j: (j, 0, 0)),
            col_spec(bk, lambda i, j: j),
        ],
        out_specs=pl.BlockSpec((None, bn, bk), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((gk, x.shape[0], bk), jnp.float32),
        interpret=interpret,
    )(x, mu[:, None, :], chol_prec, _cols(logdet_prec, bk))
    return out.transpose(1, 0, 2).reshape(x.shape[0], -1)[:n, :k]
