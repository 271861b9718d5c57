"""jit'd kernel wrappers + the paper's run-time kernel auto-selection (§4.2).

The paper picks between two CUDA matmul kernels by the d x N problem size
(crossover measured at 640,000 on a Quadro RTX 4000, overridable by the
user). We reproduce the mechanism: ``matmul_auto`` dispatches between the
Pallas blocked kernel and XLA's dot at ``MATMUL_CROSSOVER`` elements, and
the crossover for *this* host is re-measured by benchmarks/bench_kernels.py
(EXPERIMENTS §Perf).

On CPU (this container) the Pallas kernels run in ``interpret=True`` mode —
the kernel body executes in Python for correctness validation; on TPU the
same ``pl.pallas_call`` lowers through Mosaic.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import assign as _assign
from repro.kernels import loglik as _loglik
from repro.kernels import matmul as _matmul
from repro.kernels import ref
from repro.kernels import suffstats as _suffstats
from repro.kernels import sweep as _sweep

# the paper's measured CUDA crossover; bench_kernels re-measures per host
MATMUL_CROSSOVER = 640_000

# shared VMEM ceiling on the feature dim (kernels/loglik.py, suffstats.py)
MAX_KERNEL_D = _suffstats.MAX_KERNEL_D

# Per-GRID-STEP VMEM budget for the K-blocked kernels (assign, sub-assign
# and the megakernel sweeps): only a (bn, ...) point block and a (bk, ...)
# cluster tile are resident at once, so each guard scales with bk — NOT
# with K — and the effective K and d ceilings are set by HBM, not VMEM.
# The estimates count Pallas' two pipeline buffers per input/output block
# plus the kernel's largest in-register intermediates.
KERNEL_BLOCK_VMEM_BYTES = 8 * 1024 * 1024


def _fits(floats: int) -> bool:
    return floats * 4 <= KERNEL_BLOCK_VMEM_BYTES


# Default streamed cluster-tile size (see kernels/sweep.py)
K_BLOCK = _sweep.K_BLOCK

# HBM budget for the stat partials of one megakernel call. The sweeps
# write one (2K, ...) partial slab per 128-point block, as large as the
# fit's stat accumulator, so ComponentFamily.sweep runs longer inputs in
# STATS_BLOCK-aligned chunks (core/family.fold_chunked).
SWEEP_PARTIALS_BYTES = 256 * 1024 * 1024


def sweep_chunk_points(acc_bytes: int) -> int:
    """Points per megakernel call whose partials fit the budget, given the
    bytes of the (K, 2, ...) stat accumulator."""
    per_stats_block = acc_bytes * (_sweep.STATS_BLOCK // 128)
    return _sweep.STATS_BLOCK * max(1, SWEEP_PARTIALS_BYTES
                                    // per_stats_block)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def matmul_pallas(a: jax.Array, b: jax.Array, **kw) -> jax.Array:
    return _matmul.matmul(a, b, interpret=_interpret(), **kw)


def matmul_auto(a: jax.Array, b: jax.Array,
                crossover: int = MATMUL_CROSSOVER) -> jax.Array:
    """Size-dispatched matmul: Pallas ('Kernel #1') below the crossover,
    XLA dot ('Kernel #2') above — the paper's auto-selection, sizes are
    static at trace time so the dispatch costs nothing at run time."""
    size = a.shape[0] * a.shape[1]                 # the paper's d*N measure
    if size < crossover:
        return matmul_pallas(a, b)
    return ref.matmul(a, b)


def loglik_pallas(x: jax.Array, mu: jax.Array, chol_prec: jax.Array,
                  logdet_prec: jax.Array, **kw) -> jax.Array:
    return _loglik.loglik(x, mu, chol_prec, logdet_prec,
                          interpret=_interpret(), **kw)


def suffstats_pallas(x: jax.Array, resp: jax.Array, **kw
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return _suffstats.suffstats(x, resp, interpret=_interpret(), **kw)


def gauss_loglik(x: jax.Array, params, use_pallas: bool) -> jax.Array:
    """Gaussian family fast path (core/family.py): (N, K) log-likelihoods
    from a batched GaussParams pytree (core/niw.py)."""
    if use_pallas:
        return loglik_pallas(x, params.mu, params.chol_prec,
                             params.logdet_prec)
    return ref.loglik(x, params.mu, params.chol_prec, params.logdet_prec)


# ---------------------------------------------------------------------------
# Fused assignment (steps e/f) + label-indexed suff-stats (kernels/assign.py,
# kernels/suffstats.py). Every wrapper returns ``None`` when the problem
# falls outside the kernel's documented VMEM envelope, and the caller
# (core/family.py dispatch) runs the jnp reference path instead.
# ---------------------------------------------------------------------------
def assign_linear_pallas(feats, w, const, logw, active, gidx, key_data,
                         slots=None, k_block: int = K_BLOCK
                         ) -> Optional[jax.Array]:
    bn, bk, dp = 128, k_block, feats.shape[1]
    # (bn, d') feats + (bk, d') weight tile, double-buffered; logit tiles
    if not _fits(2 * (bn * dp + bk * dp) + 3 * bn * bk):
        return None
    return _assign.assign_linear(feats, w, const, logw, active, gidx,
                                 key_data, slots, bk=bk,
                                 interpret=_interpret())


def assign_gauss_pallas(x, mu, chol_prec, logdet_prec, logw, active, gidx,
                        key_data, slots=None, k_block: int = K_BLOCK
                        ) -> Optional[jax.Array]:
    bn, bk, d = 128, k_block, x.shape[1]
    # (bn, d) x + (bk, d, d) Cholesky tile, double-buffered; the
    # (bk, bn, d) diffs and whitened diffs; logit tiles
    if not _fits(2 * (bn * d + bk * d + bk * d * d) + 2 * bk * bn * d
                 + 3 * bn * bk):
        return None
    return _assign.assign_gauss(x, mu, chol_prec, logdet_prec, logw,
                                active, gidx, key_data, slots, bk=bk,
                                interpret=_interpret())


def sub_assign_linear_pallas(feats, w, const, sublogw, labels, gidx,
                             key_data, k_block: int = K_BLOCK
                             ) -> Optional[jax.Array]:
    bn, bk, dp = 128, k_block, feats.shape[1]
    # (bn, d') feats + (2bk, d') sub-weight tile, double-buffered; the
    # (bn, 2bk) likelihood / select tiles
    if not _fits(2 * (bn * dp + 2 * bk * dp) + 8 * bn * bk):
        return None
    return _assign.sub_assign_linear(feats, w, const, sublogw, labels,
                                     gidx, key_data, bk=bk,
                                     interpret=_interpret())


def sub_assign_gauss_pallas(x, mu, chol_prec, logdet_prec, sublogw, labels,
                            gidx, key_data, k_block: int = K_BLOCK
                            ) -> Optional[jax.Array]:
    bn, bk, d = 128, k_block, x.shape[1]
    # (bn, d) x + (2bk, d, d) sub-Cholesky tile, double-buffered; the
    # (2bk, bn, d) diffs and whitened diffs; select tiles
    if not _fits(2 * (bn * d + 2 * bk * d + 2 * bk * d * d)
                 + 4 * bk * bn * d + 8 * bn * bk):
        return None
    return _assign.sub_assign_gauss(x, mu, chol_prec, logdet_prec, sublogw,
                                    labels, gidx, key_data, bk=bk,
                                    interpret=_interpret())


def sweep_linear_pallas(feats, w, const, logw, active, subw, subconst,
                        sublogw, valid, gidx, key_z, key_zb, slots=None,
                        k_block: int = K_BLOCK):
    """One-read, K-blocked fused sweep (kernels/sweep.py) for linear
    families.

    Returns ``(labels, sublabels, n2, sf2)`` with per-STATS_BLOCK stat
    partials, or ``None`` outside the per-K-block VMEM envelope (the
    caller records the fallback to the blocked jnp reference). Only a
    (bk, ...) cluster tile is resident per grid step, so the guard is
    independent of K.
    """
    bn, bk, dp = 128, k_block, feats.shape[1]
    # double-buffered: (bn, d') feats, (bk, d') + (2bk, d') weight tiles,
    # the (2bk, d') stat partial tile; plus the (bn, bk) / (bn, 2bk)
    # logit, select and segment one-hot tiles
    if not _fits(2 * (bn * dp + 3 * bk * dp) + 2 * 2 * bk * dp
                 + 8 * bn * bk):
        return None
    return _sweep.sweep_linear(feats, w, const, logw, active, subw,
                               subconst, sublogw, valid, gidx, key_z,
                               key_zb, slots, bk=bk,
                               interpret=_interpret())


def sweep_gauss_pallas(x, mu, chol_prec, logdet_prec, logw, active, sub_mu,
                       sub_chol_prec, sub_logdet_prec, sublogw, valid, gidx,
                       key_z, key_zb, slots=None, k_block: int = K_BLOCK):
    """One-read, K-blocked fused sweep for the full-covariance Gaussian,
    or ``None`` outside the per-K-block VMEM envelope."""
    bn, bk, d = 128, k_block, x.shape[1]
    # double-buffered: (bn, d) x, the (bk, d, d) + (2bk, d, d) Cholesky
    # tiles with their means, the (2bk, d) + (2bk, d, d) stat partial
    # tiles; plus phase 1's (2bk, bn, d) diffs and whitened diffs, the
    # (2bk, bn, d) second-moment staging (x3: weight, transpose,
    # broadcast) and the logit / one-hot tiles
    if not _fits(2 * (bn * d + 3 * bk * d + 3 * bk * d * d)
                 + 2 * (2 * bk * d + 2 * bk * d * d)
                 + 10 * bk * bn * d + 10 * bn * bk):
        return None
    return _sweep.sweep_gauss(x, mu, chol_prec, logdet_prec, logw, active,
                              sub_mu, sub_chol_prec, sub_logdet_prec,
                              sublogw, valid, gidx, key_z, key_zb, slots,
                              bk=bk, interpret=_interpret())


def suffstats_labels_pallas(x, labels, sublabels, valid, k: int):
    if x.shape[1] > MAX_KERNEL_D:
        return None
    return _suffstats.suffstats_labels(x, labels, sublabels, valid, k,
                                       interpret=_interpret())


def moments_labels_pallas(feats, labels, sublabels, valid, k: int):
    if feats.shape[1] > 2 * MAX_KERNEL_D:
        return None
    return _suffstats.moments_labels(feats, labels, sublabels, valid, k,
                                     interpret=_interpret())


def diag_gauss_loglik(x: jax.Array, params, use_pallas: bool) -> jax.Array:
    """diag_gaussian family fast path: the quadratic expands into two
    (N, d) x (d, K) matmuls served by the paper's auto-selected matmul
    kernel (§4.2) — same hot-spot shape as the multinomial likelihood."""
    from repro.core import diag_gaussian
    return diag_gaussian.loglik(
        x, params, matmul=matmul_auto if use_pallas else ref.matmul)
