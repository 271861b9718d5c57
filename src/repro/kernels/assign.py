"""Pallas TPU kernels for the fused assignment steps (e) and (f).

The paper's GPU implementation wins by *fusing* the assignment hot path
(§4.1e, §4.4 "Kernel #1/#2"): likelihood, prior weight, categorical noise
and the argmax all happen per streaming tile, so the (N, K) logit and noise
matrices never round-trip through global memory. These kernels are the TPU
analogue — a flash-attention-style running (max, argmax) over cluster
tiles:

``assign_linear`` / ``assign_gauss``  (step e)
    grid (N/bn, K/bk) with the *cluster* axis innermost; the only VMEM
    state carried across cluster tiles is a (bn, 1) running best value and
    best index. Per tile the kernel computes loglik + logpi + Gumbel
    (counter-based Threefry keyed on the global point index —
    kernels/prng.py, bitwise-identical to the reference sweep) and folds it
    into the running pair. Labels come out directly: the (N, K) logits and
    Gumbel tensors never exist in HBM.

``sub_assign_linear`` / ``sub_assign_gauss``  (step f)
    grid (N/bn, K/bk), same streamed cluster tiles: each tile evaluates
    the (bn, 2*bk) sub-cluster likelihoods of its K-block on the MXU, and
    every point whose label falls in the block takes the two columns of
    its OWN cluster (an exact masked select). No (K, 2, ...) block is ever
    VMEM-resident and no in-kernel gather is needed.

Families plug in via two shapes of likelihood:
 - *linear*: loglik(x)_k = feats @ w_k + const_k  (multinomial, poisson,
   diag-Gaussian — see the families' ``assign_pack`` hooks), and
 - *Gaussian*: the whitening Mahalanobis form of kernels/loglik.py.

Layout (what Mosaic accepts): per-point vectors travel as (N, 1) columns
in (bn, 1) blocks; per-cluster vectors as (K/bk, 1, bk) rows or
(K/bk, bk, 1) columns whose last two block dims are whole, so any bk is
legal; the key words sit in SMEM. Inside a kernel, logit tiles are
(bn, bk) and every reduction keeps its dims.

All kernels mirror the reference sweep's op order exactly
(ll + logpi, mask, + Gumbel, first-max argmax), so interpret-mode labels
match the jnp path bitwise except on exact floating-point argmax ties
(probability ~0 under continuous Gumbel noise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import prng

LOG_2PI = 1.8378770664093453
# Inactive-cluster mask, canonical: core.family imports it from here so the
# constant baked into the kernels' tile masking can never drift from the
# reference sweep's.
NEG_INF = -1e30

# the two raw Threefry key words, read as scalars
KEY_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _pad_dim(a: jax.Array, axis: int, pad: int, value=0) -> jax.Array:
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _rows(v: jax.Array, bk: int) -> jax.Array:
    """(K,) per-cluster vector -> (K/bk, 1, bk): one (1, bk) row per
    K-block (``row_spec``)."""
    return v.reshape(-1, 1, bk)


def _cols(v: jax.Array, bk: int) -> jax.Array:
    """(K,) per-cluster vector -> (K/bk, bk, 1): one (bk, 1) column per
    K-block (``col_spec``)."""
    return v.reshape(-1, bk, 1)


def row_spec(bk: int, kmap) -> pl.BlockSpec:
    return pl.BlockSpec((None, 1, bk), lambda *g: (kmap(*g), 0, 0))


def col_spec(bk: int, kmap) -> pl.BlockSpec:
    return pl.BlockSpec((None, bk, 1), lambda *g: (kmap(*g), 0, 0))


def _fold_best(j, bk, total, best_ref, lab_ref):
    """Fold a (bn, bk) logit tile into the running (max, argmax) pair."""
    tile_best = jnp.max(total, axis=1, keepdims=True)
    tile_arg = (jnp.argmax(total, axis=1, keepdims=True).astype(jnp.int32)
                + j * bk)
    improve = tile_best > best_ref[...]  # strict: keep FIRST max, like argmax
    lab_ref[...] = jnp.where(improve, tile_arg, lab_ref[...])
    best_ref[...] = jnp.where(improve, tile_best, best_ref[...])


def _noisy_logits(ll, logw, act, slot, key_ref, gidx):
    """Step (e) logits of one tile: (ll + log pi), inactive masked, plus
    the slot-keyed Gumbel draw. ``ll`` (bn, bk); ``logw``/``act``/``slot``
    (1, bk) rows; ``gidx`` (bn, 1)."""
    t = ll + logw
    t = jnp.where(act != 0, t, NEG_INF)
    # Gumbel counter = the cluster's SLOT id (== its compact position on the
    # dense slab), so compacted slabs draw the exact noise of the full slab
    cid = jnp.broadcast_to(slot, t.shape)
    return t + prng.gumbel(key_ref, gidx, cid)


def gauss_block_ll(x, mu, f, ld, d: int):
    """(bn, b) Gaussian log-likelihoods of the b clusters of one tile:
    x (bn, d); mu (b, 1, d); f (b, d, d); ld (b, 1). The whitening
    y = (x - mu_k) @ F_k runs batched over the b clusters on the MXU, in
    the contraction order of kernels/loglik.py / core/niw.py."""
    diff = x[None, :, :] - mu                        # (b, bn, d)
    y = jax.lax.dot_general(
        diff, f, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)          # (b, bn, d)
    maha = jnp.sum(y * y, axis=-1)                   # (b, bn)
    return (0.5 * (ld - maha) - 0.5 * d * LOG_2PI).T


def own_sub_labels(ll2, sublogw, loc, key_ref, gidx):
    """Step (f) for the points of one K-block: ``ll2`` (bn, 2b) holds the
    sub-cluster log-likelihoods of the block's b clusters (column 2k + s),
    ``sublogw`` the matching (1, 2b) row, ``loc`` (bn, 1) the block-local
    labels. Each point keeps the two columns of its OWN cluster — a masked
    sum of one value, which is exact — then the reference op order
    (ll + logw, + Gumbel over s in {0, 1}, first-max argmax). Rows owned by
    another block get garbage the caller masks out."""
    t = ll2 + sublogw
    col = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    t = t + prng.gumbel(key_ref, gidx, (col & 1).astype(jnp.uint32))
    pick = lambda s: jnp.sum(jnp.where(col == 2 * loc + s, t, 0.0), axis=1,
                             keepdims=True)
    t0, t1 = pick(0), pick(1)
    # argmax over (t0, t1): first max wins, NaN counts as the max
    take1 = (t1 > t0) | (jnp.isnan(t1) & ~jnp.isnan(t0))
    return take1.astype(jnp.int32)


def linear_sub_ll(feats, subw, subconst):
    """(bn, 2b) sub-cluster log-likelihoods of a linear family's K-block:
    ``subw`` (2b, d') rows, ``subconst`` (1, 2b)."""
    return (jnp.dot(feats, subw.T, preferred_element_type=jnp.float32)
            + subconst)


# ---------------------------------------------------------------------------
# Step (e): cluster assignment
# ---------------------------------------------------------------------------
def _assign_linear_kernel(feats_ref, w_ref, const_ref, logw_ref, act_ref,
                          slot_ref, gidx_ref, key_ref, best_ref, lab_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, NEG_INF)
        lab_ref[...] = jnp.zeros_like(lab_ref)

    bk = w_ref.shape[0]
    ll = (jnp.dot(feats_ref[...], w_ref[...].T,
                  preferred_element_type=jnp.float32)
          + const_ref[...])                           # (bn, bk) loglik tile
    t = _noisy_logits(ll, logw_ref[...], act_ref[...], slot_ref[...],
                      key_ref, gidx_ref[...])
    _fold_best(j, bk, t, best_ref, lab_ref)


def _assign_gauss_kernel(x_ref, mu_ref, f_ref, ld_ref, logw_ref, act_ref,
                         slot_ref, gidx_ref, key_ref, best_ref, lab_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, NEG_INF)
        lab_ref[...] = jnp.zeros_like(lab_ref)

    bk, _, d = mu_ref.shape
    ll = gauss_block_ll(x_ref[...], mu_ref[...], f_ref[...], ld_ref[...], d)
    t = _noisy_logits(ll, logw_ref[...], act_ref[...], slot_ref[...],
                      key_ref, gidx_ref[...])
    _fold_best(j, bk, t, best_ref, lab_ref)


def _point_spec(bn: int) -> pl.BlockSpec:
    """(bn, 1) block of an (N, 1) per-point column; grid axis 0 = points."""
    return pl.BlockSpec((bn, 1), lambda i, *_: (i, 0))


def _run_assign(kernel, n, bn, gk, args, specs, interpret):
    gn = args[0].shape[0] // bn
    _, labels = pl.pallas_call(
        kernel,
        grid=(gn, gk),                       # K innermost: running argmax
        in_specs=specs,
        out_specs=[_point_spec(bn), _point_spec(bn)],   # revisited over j
        out_shape=[
            jax.ShapeDtypeStruct((args[0].shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((args[0].shape[0], 1), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    return labels[:n, 0]


def _cluster_vectors(bk, pk, logw, active, slots):
    return (_rows(_pad_dim(logw, 0, pk), bk),
            _rows(_pad_dim(active.astype(jnp.int32), 0, pk), bk),  # pad off
            _rows(_pad_dim(slots.astype(jnp.uint32), 0, pk), bk))


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "interpret"))
def assign_linear(feats: jax.Array, w: jax.Array, const: jax.Array,
                  logw: jax.Array, active: jax.Array, gidx: jax.Array,
                  key_data: jax.Array, slots: jax.Array = None, *,
                  bn: int = 128, bk: int = 8,
                  interpret: bool = False) -> jax.Array:
    """Fused step (e) for linear-likelihood families -> (N,) int32 labels.

    feats: (N, d'); w: (K, d'); const/logw: (K,); active: (K,) bool;
    gidx: (N,) uint32 global point indices; key_data: (2,) uint32.
    ``slots``: (K,) uint32 dense-slab slot ids used as Gumbel counters
    (defaults to ``arange(K)`` — the dense identity); a compacted caller
    passes the gathered slot ids so labels stay bitwise the dense sweep's.
    """
    n, dp = feats.shape
    k = w.shape[0]
    if slots is None:
        slots = jnp.arange(k, dtype=jnp.uint32)
    bn = min(bn, n) or 1
    bk = min(bk, k) or 1
    pn, pk = (-n) % bn, (-k) % bk
    blk = lambda i, j: j
    args = (_pad_dim(feats, 0, pn), _pad_dim(w, 0, pk),
            _rows(_pad_dim(const, 0, pk), bk),
            *_cluster_vectors(bk, pk, logw, active, slots),
            _pad_dim(gidx.astype(jnp.uint32), 0, pn)[:, None], key_data)
    specs = [
        pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
        pl.BlockSpec((bk, dp), lambda i, j: (j, 0)),
        row_spec(bk, blk), row_spec(bk, blk), row_spec(bk, blk),
        row_spec(bk, blk),
        _point_spec(bn),
        KEY_SPEC,
    ]
    return _run_assign(_assign_linear_kernel, n, bn, (k + pk) // bk, args,
                       specs, interpret)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "interpret"))
def assign_gauss(x: jax.Array, mu: jax.Array, chol_prec: jax.Array,
                 logdet_prec: jax.Array, logw: jax.Array,
                 active: jax.Array, gidx: jax.Array, key_data: jax.Array,
                 slots: jax.Array = None, *, bn: int = 128, bk: int = 8,
                 interpret: bool = False) -> jax.Array:
    """Fused step (e) for the full-covariance Gaussian -> (N,) labels."""
    n, d = x.shape
    k = mu.shape[0]
    if slots is None:
        slots = jnp.arange(k, dtype=jnp.uint32)
    bn = min(bn, n) or 1
    bk = min(bk, k) or 1
    pn, pk = (-n) % bn, (-k) % bk
    if pk:
        eye = jnp.broadcast_to(jnp.eye(d, dtype=chol_prec.dtype),
                               (pk, d, d))
        chol_prec = jnp.concatenate([chol_prec, eye], axis=0)
    blk = lambda i, j: j
    args = (_pad_dim(x, 0, pn), _pad_dim(mu, 0, pk)[:, None, :], chol_prec,
            _cols(_pad_dim(logdet_prec, 0, pk), bk),
            *_cluster_vectors(bk, pk, logw, active, slots),
            _pad_dim(gidx.astype(jnp.uint32), 0, pn)[:, None], key_data)
    specs = [
        pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
        pl.BlockSpec((bk, 1, d), lambda i, j: (j, 0, 0)),
        pl.BlockSpec((bk, d, d), lambda i, j: (j, 0, 0)),
        col_spec(bk, blk), row_spec(bk, blk), row_spec(bk, blk),
        row_spec(bk, blk),
        _point_spec(bn),
        KEY_SPEC,
    ]
    return _run_assign(_assign_gauss_kernel, n, bn, (k + pk) // bk, args,
                       specs, interpret)


# ---------------------------------------------------------------------------
# Step (f): own-cluster sub-assignment, K-blocked
# ---------------------------------------------------------------------------
def _sub_update(j, bk, ll2, sublogw_ref, lab_ref, gidx_ref, key_ref,
                out_ref):
    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    loc = lab_ref[...] - j * bk                      # block-local label
    in_blk = (loc >= 0) & (loc < bk)
    sub = own_sub_labels(ll2, sublogw_ref[...], loc, key_ref, gidx_ref[...])
    out_ref[...] = jnp.where(in_blk, sub, out_ref[...])


def _sub_assign_linear_kernel(feats_ref, w_ref, const_ref, sublogw_ref,
                              lab_ref, gidx_ref, key_ref, out_ref):
    j = pl.program_id(1)
    ll2 = linear_sub_ll(feats_ref[...], w_ref[...], const_ref[...])
    _sub_update(j, w_ref.shape[0] // 2, ll2, sublogw_ref, lab_ref,
                gidx_ref, key_ref, out_ref)


def _sub_assign_gauss_kernel(x_ref, mu_ref, f_ref, ld_ref, sublogw_ref,
                             lab_ref, gidx_ref, key_ref, out_ref):
    j = pl.program_id(1)
    d = x_ref.shape[1]
    ll2 = gauss_block_ll(x_ref[...], mu_ref[...], f_ref[...], ld_ref[...], d)
    _sub_update(j, mu_ref.shape[0] // 2, ll2, sublogw_ref, lab_ref,
                gidx_ref, key_ref, out_ref)


def _run_sub_assign(kernel, n, bn, gk, args, specs, interpret):
    gn = args[0].shape[0] // bn
    out = pl.pallas_call(
        kernel,
        grid=(gn, gk),
        in_specs=specs + [_point_spec(bn), _point_spec(bn), KEY_SPEC],
        out_specs=_point_spec(bn),                   # revisited over j
        out_shape=jax.ShapeDtypeStruct((args[0].shape[0], 1), jnp.int32),
        interpret=interpret,
    )(*args)
    return out[:n, 0]


def _sub_points(labels, gidx, key_data, pn):
    return (_pad_dim(labels.astype(jnp.int32), 0, pn)[:, None],
            _pad_dim(gidx.astype(jnp.uint32), 0, pn)[:, None], key_data)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def sub_assign_linear(feats: jax.Array, w: jax.Array, const: jax.Array,
                      sublogw: jax.Array, labels: jax.Array,
                      gidx: jax.Array, key_data: jax.Array, *,
                      bn: int = 128, bk: int = 8,
                      interpret: bool = False) -> jax.Array:
    """Fused step (f) for linear families -> (N,) int32 sub-labels.

    feats: (N, d'); w: (K, 2, d'); const/sublogw: (K, 2); labels: (N,).
    """
    n, dp = feats.shape
    k = w.shape[0]
    bn = min(bn, n) or 1
    bk = min(bk, k) or 1
    pn, pk = (-n) % bn, (-k) % bk
    blk = lambda i, j: j
    args = (_pad_dim(feats, 0, pn),
            _pad_dim(w, 0, pk).reshape(-1, dp),
            _rows(_pad_dim(const, 0, pk).reshape(-1), 2 * bk),
            _rows(_pad_dim(sublogw, 0, pk).reshape(-1), 2 * bk),
            *_sub_points(labels, gidx, key_data, pn))
    specs = [
        pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
        pl.BlockSpec((2 * bk, dp), lambda i, j: (j, 0)),
        row_spec(2 * bk, blk), row_spec(2 * bk, blk),
    ]
    return _run_sub_assign(_sub_assign_linear_kernel, n, bn, (k + pk) // bk,
                           args, specs, interpret)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def sub_assign_gauss(x: jax.Array, mu: jax.Array, chol_prec: jax.Array,
                     logdet_prec: jax.Array, sublogw: jax.Array,
                     labels: jax.Array, gidx: jax.Array,
                     key_data: jax.Array, *, bn: int = 128, bk: int = 8,
                     interpret: bool = False) -> jax.Array:
    """Fused step (f) for the Gaussian -> (N,) int32 sub-labels.

    x: (N, d); mu: (K, 2, d); chol_prec: (K, 2, d, d); logdet/sublogw:
    (K, 2). Streams (bk, 2, ...) sub-parameter tiles.
    """
    n, d = x.shape
    k = mu.shape[0]
    bn = min(bn, n) or 1
    bk = min(bk, k) or 1
    pn, pk = (-n) % bn, (-k) % bk
    blk = lambda i, j: j
    args = (_pad_dim(x, 0, pn),
            _pad_dim(mu, 0, pk).reshape(-1, 1, d),
            _pad_dim(chol_prec, 0, pk).reshape(-1, d, d),
            _cols(_pad_dim(logdet_prec, 0, pk).reshape(-1), 2 * bk),
            _rows(_pad_dim(sublogw, 0, pk).reshape(-1), 2 * bk),
            *_sub_points(labels, gidx, key_data, pn))
    specs = [
        pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
        pl.BlockSpec((2 * bk, 1, d), lambda i, j: (j, 0, 0)),
        pl.BlockSpec((2 * bk, d, d), lambda i, j: (j, 0, 0)),
        col_spec(2 * bk, blk), row_spec(2 * bk, blk),
    ]
    return _run_sub_assign(_sub_assign_gauss_kernel, n, bn, (k + pk) // bk,
                           args, specs, interpret)
