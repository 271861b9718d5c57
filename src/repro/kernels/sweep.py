"""Pallas TPU megakernels for the ONE-READ fused sweep (steps e + f +
suff-stat fold in a single pass over x), K-BLOCKED so only a (bk, ...)
cluster tile is ever VMEM-resident.

After the assignment fusion (kernels/assign.py) and the label-indexed
suff-stats (kernels/suffstats.py), the sweep was still three separate
passes over the point tile — step (e), step (f), and the stat fold each
streamed every byte of ``x`` (or its ``assign_pack`` features) from HBM
once per iteration, and the linear families recomputed the feature
transform in each pass. These kernels collapse the three into one
``pallas_call`` whose only large operand is ``x``: while a point block is
resident in VMEM it is

 1. assigned (step e: loglik + log pi + counter-based Threefry Gumbel,
    a flash-attention-style running argmax over *streamed* (bk, ...)
    cluster tiles — never the full (K, ...) slab),
 2. sub-assigned under its OWN cluster only (step f: the owning K-block's
    2*bk sub-cluster likelihoods on the MXU, each point keeping its own
    cluster's pair by an exact masked select — no in-kernel gather), and
 3. folded into per-(point-block, K-block) stat partial tiles

— labels, sub-labels, and the stat partials stream out; the block of
``x`` is never touched again. HBM traffic per sweep stays at one read of
x, and VMEM per grid step is O(bn + bk): K (and d) are bounded by HBM,
not by an all-K-resident VMEM budget.

Grid layout: ``(gn, 2, gk)`` — point blocks outermost, then a 2-step
*phase* axis, then K-blocks innermost. Phase 0 streams the gk cluster
tiles through the running (max, argmax) pair exactly like
``kernels/assign.py`` (strict ``>`` keeps the FIRST max, so the fold is
bitwise the full argmax). Phase 1 revisits the gk tiles to sub-assign and
fold stats for the points each tile OWNS (label in [j*bk, (j+1)*bk)) —
each (i, j) stat tile is written exactly once, and the label/sub-label
output blocks are revisited only consecutively (all phases of one point
block), which is the Pallas TPU revolving-buffer contract.

The stat partials come out per (point block, K block); the *caller* folds
them into per-``STATS_BLOCK`` partials with a left-to-right add chain
starting from +0.0 — the exact float addition sequence the previous
all-K-resident kernel ran in VMEM (zero-init then ``+=`` per point
block), so chains are bitwise unchanged. Partials are then folded
left-to-right by core/family.py as before.

Cluster identity: every kernel takes a ``slots`` operand — the (K,)
uint32 dense-slab slot ids, used as the Gumbel counters. A compacted
caller (core/gibbs.py's active-set compaction) passes the gathered slot
ids so the noise — hence the chain — is bitwise the dense slab's; dense
callers pass ``arange(K)``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.assign import (KEY_SPEC, NEG_INF, _cluster_vectors,
                                  _cols, _fold_best, _noisy_logits, _pad_dim,
                                  _rows, col_spec, gauss_block_ll,
                                  linear_sub_ll, own_sub_labels, row_spec)

# Granularity of the suff-stat fold — the system-wide contract (re-exported
# by core/gibbs.py): partial stats are produced per STATS_BLOCK points and
# added left to right in global point order on EVERY path, so the float
# addition sequence — hence every bit of the chain — is invariant to tile
# size and sharding. Changing this constant changes chains.
STATS_BLOCK = 1024

# Default cluster-tile size streamed through VMEM (bk): mirrors
# kernels/assign.py's step-(e) tiling.
K_BLOCK = 8


def _pad_points(arrs, bn: int):
    out = []
    for a in arrs:
        out.append(_pad_dim(a, 0, (-a.shape[0]) % bn))
    return out


def _fold_stats(a: jax.Array, spb: int) -> jax.Array:
    """(gn, ...) per-point-block partials -> (nsb, ...) per-STATS_BLOCK.

    Left-to-right adds from +0.0 in point-block order: the exact chain the
    old in-kernel accumulator ran (zero-init at each stats-block boundary,
    then one ``+=`` per point block), so the per-STATS_BLOCK partials are
    bitwise unchanged. Ragged trailing blocks are padded with zero rows
    (x + 0.0 == x after a +0.0 start, so padding is a no-op bitwise).
    """
    gn = a.shape[0]
    nsb = -(-gn // spb)
    a = _pad_dim(a, 0, nsb * spb - gn)
    a = a.reshape((nsb, spb) + a.shape[1:])
    out = jnp.zeros((nsb,) + a.shape[2:], a.dtype)
    for t in range(spb):
        out = out + a[:, t]
    return out


def _seg_onehot_block(loc, sub, valid, s: int):
    """(bn, 2*bk) one-hot over the K-block's segments 2*loc + sub.

    ``loc``/``sub``/``valid`` are (bn, 1) columns; ``loc`` is the
    block-local label, so rows owned by other K-blocks fall outside
    [0, s) and contribute all-zero rows: the per-column sums are exactly
    the full-width one-hot's columns for this block.
    """
    seg = loc * 2 + sub
    col = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], s), 1)
    return (seg == col).astype(jnp.float32) * valid


def _sweep_specs(bn: int):
    """Point-side specs shared by both megakernels: the (bn, 1) columns of
    valid / gidx, the two SMEM key pairs, and the outputs' label columns."""
    point = pl.BlockSpec((bn, 1), lambda i, p, j: (i, 0))
    return [point, point, KEY_SPEC, KEY_SPEC], [point, point, point]


def _label_shapes(n_pad: int):
    return [jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32)]


# Stat partial outputs: one (2bk, ...) tile per (point block, K block).
# The block index is held at (i, 0) through phase 0, then visits (i, j)
# once in phase 1; counts ride in (gn, gk, 1, 2bk) so the block's last two
# dims are whole.
def _n_spec(sb: int) -> pl.BlockSpec:
    return pl.BlockSpec((None, None, 1, sb), lambda i, p, j: (i, j * p, 0, 0))


def _stat_spec(sb: int, *tail: int) -> pl.BlockSpec:
    zeros = (0,) * len(tail)
    return pl.BlockSpec((None, sb) + tail, lambda i, p, j: (i, j * p) + zeros)


# ---------------------------------------------------------------------------
# Linear-likelihood families (multinomial / poisson / diag-Gaussian):
# the stat features ARE the assign_pack features (x, or [x, x^2]), so the
# whole sweep shares one resident feature block.
# ---------------------------------------------------------------------------
def _sweep_linear_kernel(feats_ref, w_ref, const_ref, logw_ref, act_ref,
                         slot_ref, subw_ref, subconst_ref, sublogw_ref,
                         valid_ref, gidx_ref, kz_ref, kzb_ref,
                         best_ref, lab_ref, sub_ref, n_ref, sf_ref):
    p = pl.program_id(1)
    j = pl.program_id(2)
    bk = w_ref.shape[0]
    feats = feats_ref[...]                               # the ONE x read
    gidx = gidx_ref[...]

    @pl.when((p == 0) & (j == 0))
    def _init():
        best_ref[...] = jnp.full_like(best_ref, NEG_INF)
        lab_ref[...] = jnp.zeros_like(lab_ref)
        sub_ref[...] = jnp.zeros_like(sub_ref)

    @pl.when(p == 0)
    def _assign():
        # step (e) on one streamed cluster tile: same op order as
        # kernels/assign._assign_linear_kernel (ll + logpi, mask, + Gumbel,
        # strict first-max fold) — bitwise the full argmax.
        ll = (jnp.dot(feats, w_ref[...].T,
                      preferred_element_type=jnp.float32)
              + const_ref[...])
        t = _noisy_logits(ll, logw_ref[...], act_ref[...], slot_ref[...],
                          kz_ref, gidx)
        _fold_best(j, bk, t, best_ref, lab_ref)

    @pl.when(p == 1)
    def _sub_and_stats():
        # step (f) + stat fold for the points THIS K-block owns: the
        # block's (bn, 2bk) sub-likelihoods, each point keeping its own
        # cluster's pair (kernels/assign.sub_assign_linear)
        loc = lab_ref[...] - j * bk                      # block-local label
        in_blk = (loc >= 0) & (loc < bk)
        ll2 = linear_sub_ll(feats, subw_ref[...], subconst_ref[...])
        sub = own_sub_labels(ll2, sublogw_ref[...], loc, kzb_ref, gidx)
        sub = jnp.where(in_blk, sub, sub_ref[...])
        sub_ref[...] = sub
        r = _seg_onehot_block(loc, sub, valid_ref[...], n_ref.shape[1])
        n_ref[...] = jnp.sum(r, axis=0, keepdims=True)
        sf_ref[...] = jnp.dot(r.T, feats, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def sweep_linear(feats: jax.Array, w: jax.Array, const: jax.Array,
                 logw: jax.Array, active: jax.Array, subw: jax.Array,
                 subconst: jax.Array, sublogw: jax.Array, valid: jax.Array,
                 gidx: jax.Array, key_z: jax.Array, key_zb: jax.Array,
                 slots: jax.Array = None, *, bn: int = 128,
                 bk: int = K_BLOCK, interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One-read, K-blocked fused sweep for linear-likelihood families.

    feats: (N, d') assign_pack features (shared by steps e/f AND the stat
    fold); w: (K, d'); const/logw: (K,); active: (K,) bool/int;
    subw: (K, 2, d'); subconst/sublogw: (K, 2); valid: (N,); gidx: (N,)
    uint32; key_z/key_zb: (2,) uint32; slots: (K,) uint32 dense-slab slot
    ids for the Gumbel counters (default ``arange(K)``).

    Returns ``(labels (N,), sublabels (N,), n2 (nsb, K, 2),
    sf2 (nsb, K, 2, d'))`` where the trailing pair are per-STATS_BLOCK
    stat partials to be folded left-to-right by the caller. Only a
    (bk, ...) cluster tile is VMEM-resident at any grid step.
    """
    assert STATS_BLOCK % bn == 0, "bn must divide the stats fold block"
    n, dp = feats.shape
    k = w.shape[0]
    if slots is None:
        slots = jnp.arange(k, dtype=jnp.uint32)
    bk = min(bk, k) or 1
    feats, valid, gidx = _pad_points(
        (feats, jnp.asarray(valid, jnp.float32)[:, None],
         gidx.astype(jnp.uint32)[:, None]), bn)
    pk = (-k) % bk
    k_pad = k + pk
    sb = 2 * bk
    gn = feats.shape[0] // bn
    gk = k_pad // bk
    spb = STATS_BLOCK // bn
    nsb = -(-gn // spb)
    blk = lambda i, p, j: j
    point_in, point_out = _sweep_specs(bn)

    _, labels, sublabels, n2, sf2 = pl.pallas_call(
        _sweep_linear_kernel,
        grid=(gn, 2, gk),             # phase then K innermost, sequential
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i, p, j: (i, 0)),
            pl.BlockSpec((bk, dp), lambda i, p, j: (j, 0)),   # streamed tile
            row_spec(bk, blk), row_spec(bk, blk), row_spec(bk, blk),
            row_spec(bk, blk),
            pl.BlockSpec((sb, dp), lambda i, p, j: (j, 0)),
            row_spec(sb, blk), row_spec(sb, blk),
            *point_in,
        ],
        out_specs=[*point_out, _n_spec(sb), _stat_spec(sb, dp)],
        out_shape=[
            *_label_shapes(feats.shape[0]),
            jax.ShapeDtypeStruct((gn, gk, 1, sb), jnp.float32),
            jax.ShapeDtypeStruct((gn, 2 * k_pad, dp), jnp.float32),
        ],
        interpret=interpret,
    )(feats, _pad_dim(w, 0, pk), _rows(_pad_dim(const, 0, pk), bk),
      *_cluster_vectors(bk, pk, logw, active, slots),
      _pad_dim(subw, 0, pk).reshape(-1, dp),
      _rows(_pad_dim(subconst, 0, pk).reshape(-1), sb),
      _rows(_pad_dim(sublogw, 0, pk).reshape(-1), sb),
      valid, gidx, key_z, key_zb)
    n2 = _fold_stats(n2.reshape(gn, -1), spb).reshape(nsb, k_pad, 2)[:, :k]
    sf2 = _fold_stats(sf2, spb).reshape(nsb, k_pad, 2, dp)[:, :k]
    return labels[:n, 0], sublabels[:n, 0], n2, sf2


# ---------------------------------------------------------------------------
# Full-covariance Gaussian: whitening-Mahalanobis assignment, own-cluster
# sub-assignment, second-moment stat fold — one resident x block, streamed
# (bk, d, d) Cholesky tiles.
# ---------------------------------------------------------------------------
def _sweep_gauss_kernel(x_ref, mu_ref, f_ref, ld_ref, logw_ref, act_ref,
                        slot_ref, smu_ref, sfchol_ref, sld_ref, sublogw_ref,
                        valid_ref, gidx_ref, kz_ref, kzb_ref,
                        best_ref, lab_ref, sub_ref, n_ref, sx_ref, sxx_ref):
    p = pl.program_id(1)
    j = pl.program_id(2)
    bk, _, d = mu_ref.shape
    x = x_ref[...]                                       # the ONE x read
    gidx = gidx_ref[...]

    @pl.when((p == 0) & (j == 0))
    def _init():
        best_ref[...] = jnp.full_like(best_ref, NEG_INF)
        lab_ref[...] = jnp.zeros_like(lab_ref)
        sub_ref[...] = jnp.zeros_like(sub_ref)

    @pl.when(p == 0)
    def _assign():
        # step (e): mirror of kernels/assign._assign_gauss_kernel on one
        # streamed (bk, d, d) Cholesky tile
        ll = gauss_block_ll(x, mu_ref[...], f_ref[...], ld_ref[...], d)
        t = _noisy_logits(ll, logw_ref[...], act_ref[...], slot_ref[...],
                          kz_ref, gidx)
        _fold_best(j, bk, t, best_ref, lab_ref)

    @pl.when(p == 1)
    def _sub_and_stats():
        # step (f): mirror of kernels/assign._sub_assign_gauss_kernel — the
        # block's 2bk sub-cluster likelihoods on the MXU, each point
        # keeping its own cluster's pair
        loc = lab_ref[...] - j * bk
        in_blk = (loc >= 0) & (loc < bk)
        ll2 = gauss_block_ll(x, smu_ref[...], sfchol_ref[...], sld_ref[...],
                             d)
        sub = own_sub_labels(ll2, sublogw_ref[...], loc, kzb_ref, gidx)
        sub = jnp.where(in_blk, sub, sub_ref[...])
        sub_ref[...] = sub

        # stat fold: mirror of kernels/suffstats._suffstats_labels_kernel
        # restricted to this K-block's 2*bk segments
        r = _seg_onehot_block(loc, sub, valid_ref[...], n_ref.shape[1])
        n_ref[...] = jnp.sum(r, axis=0, keepdims=True)
        sx_ref[...] = jnp.dot(r.T, x, preferred_element_type=jnp.float32)
        xw = r.T[:, :, None] * x[None, :, :]             # (2bk, bn, d)
        sxx_ref[...] = jax.lax.dot_general(
            xw.transpose(0, 2, 1),
            jnp.broadcast_to(x, (r.shape[1],) + x.shape),
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def sweep_gauss(x: jax.Array, mu: jax.Array, chol_prec: jax.Array,
                logdet_prec: jax.Array, logw: jax.Array, active: jax.Array,
                sub_mu: jax.Array, sub_chol_prec: jax.Array,
                sub_logdet_prec: jax.Array, sublogw: jax.Array,
                valid: jax.Array, gidx: jax.Array, key_z: jax.Array,
                key_zb: jax.Array, slots: jax.Array = None, *,
                bn: int = 128, bk: int = K_BLOCK, interpret: bool = False
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                           jax.Array]:
    """One-read, K-blocked fused sweep for the full-covariance Gaussian.

    x: (N, d); mu: (K, d); chol_prec: (K, d, d); logdet_prec/logw: (K,);
    sub_*: the (K, 2, ...) sub-cluster analogues; valid: (N,);
    gidx: (N,) uint32; slots: (K,) uint32 slot-id Gumbel counters.
    Returns ``(labels, sublabels, n2 (nsb, K, 2), sx2 (nsb, K, 2, d),
    sxx2 (nsb, K, 2, d, d))`` with per-STATS_BLOCK stat partials. Only a
    (bk, d, d) cluster tile is VMEM-resident at any grid step.
    """
    assert STATS_BLOCK % bn == 0, "bn must divide the stats fold block"
    n, d = x.shape
    k = mu.shape[0]
    if slots is None:
        slots = jnp.arange(k, dtype=jnp.uint32)
    bk = min(bk, k) or 1
    x, valid, gidx = _pad_points(
        (x, jnp.asarray(valid, jnp.float32)[:, None],
         gidx.astype(jnp.uint32)[:, None]), bn)
    pk = (-k) % bk
    k_pad = k + pk
    sb = 2 * bk
    gn = x.shape[0] // bn
    gk = k_pad // bk
    spb = STATS_BLOCK // bn
    nsb = -(-gn // spb)
    blk = lambda i, p, j: j
    point_in, point_out = _sweep_specs(bn)

    _, labels, sublabels, n2, sx2, sxx2 = pl.pallas_call(
        _sweep_gauss_kernel,
        grid=(gn, 2, gk),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, p, j: (i, 0)),
            pl.BlockSpec((bk, 1, d), lambda i, p, j: (j, 0, 0)),
            pl.BlockSpec((bk, d, d), lambda i, p, j: (j, 0, 0)),
            col_spec(bk, blk), row_spec(bk, blk), row_spec(bk, blk),
            row_spec(bk, blk),
            pl.BlockSpec((sb, 1, d), lambda i, p, j: (j, 0, 0)),
            pl.BlockSpec((sb, d, d), lambda i, p, j: (j, 0, 0)),
            col_spec(sb, blk), row_spec(sb, blk),
            *point_in,
        ],
        out_specs=[*point_out, _n_spec(sb), _stat_spec(sb, d),
                   _stat_spec(sb, d, d)],
        out_shape=[
            *_label_shapes(x.shape[0]),
            jax.ShapeDtypeStruct((gn, gk, 1, sb), jnp.float32),
            jax.ShapeDtypeStruct((gn, 2 * k_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((gn, 2 * k_pad, d, d), jnp.float32),
        ],
        interpret=interpret,
    )(x, _pad_dim(mu, 0, pk)[:, None, :], _pad_dim(chol_prec, 0, pk),
      _cols(_pad_dim(logdet_prec, 0, pk), bk),
      *_cluster_vectors(bk, pk, logw, active, slots),
      _pad_dim(sub_mu, 0, pk).reshape(-1, 1, d),
      _pad_dim(sub_chol_prec, 0, pk).reshape(-1, d, d),
      _cols(_pad_dim(sub_logdet_prec, 0, pk).reshape(-1), sb),
      _rows(_pad_dim(sublogw, 0, pk).reshape(-1), sb),
      valid, gidx, key_z, key_zb)
    n2 = _fold_stats(n2.reshape(gn, -1), spb).reshape(nsb, k_pad, 2)[:, :k]
    sx2 = _fold_stats(sx2, spb).reshape(nsb, k_pad, 2, d)[:, :k]
    sxx2 = _fold_stats(sxx2, spb).reshape(nsb, k_pad, 2, d, d)[:, :k]
    return labels[:n, 0], sublabels[:n, 0], n2, sx2, sxx2
