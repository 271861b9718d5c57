"""Pallas TPU kernels for per-cluster sufficient statistics — the paper's
per-stream suff-stat accumulation (§4.4, 3-step update), as masked matmuls.

Two generations of kernel live here:

``suffstats`` (dense responsibilities)
    Given points x (N, d) and responsibilities resp (N, K) (one-hot labels,
    or label x sub-label products for the sub-cluster stats):
        n_k  = sum_i r_ik          (K,)
        sx_k = sum_i r_ik x_i      (K, d)     = resp^T @ x        (MXU)
        sxx_k = sum_i r_ik x_i x_i^T (K,d,d)  = batched (d,bn)@(bn,d) per k
    The caller must materialize resp in HBM — kept as the dense oracle.

``suffstats_labels`` / ``moments_labels`` (label-indexed, the hot path)
    Take int32 ``labels``/``sublabels``/``valid`` directly and build the
    one-hot *per tile in VMEM* over segments s = 2*label + sublabel, so no
    (N, K) or (N, K, 2) responsibility tensor ever exists in HBM. One pass
    over x yields the (K, 2, ...) sub-cluster stats; cluster stats are the
    fold over the sub axis (core/gibbs.compute_stats). ``moments_labels``
    is the first-moment-only variant serving the feature-separable families
    (multinomial / poisson / diag-Gaussian via stacked [x, x^2] features).

Tiling: grid (S/bk, N/bn) with the N axis innermost and *revisited*: the
output tiles stay resident in VMEM and accumulate across N steps — the TPU
analogue of the paper's per-stream partial sums, with the cross-device psum
happening outside the kernel. Labels travel as (N, 1) columns and counts
leave as (S/bk, 1, bk) rows, so every block's last two dims are
tile-aligned or whole, as Mosaic requires; the dense kernel takes resp
transposed, (K, N), for the same reason.
VMEM (bk=8, bn=128, d<=128): x 64k + resp 4k + sxx 512k + masked 512k f32.
``MAX_KERNEL_D`` guards that budget: the (bk, d, d) output tile and the
(bk, bn, d) masked intermediate grow as d^2 / d, so d > 128 would blow the
~16 MiB VMEM; callers (kernels/ops.py) fall back to the jnp reference
(kernels/ref.py or the families' segment-sum paths) above it.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref

# VMEM ceiling for the feature dimension (see module docstring); above it
# every entry point here returns the jnp reference result instead. This is
# THE canonical kernel-d guard: loglik.py and ops.py import it from here.
MAX_KERNEL_D = 128


def _suffstats_kernel(x_ref, rt_ref, n_ref, sx_ref, sxx_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        n_ref[...] = jnp.zeros_like(n_ref)
        sx_ref[...] = jnp.zeros_like(sx_ref)
        sxx_ref[...] = jnp.zeros_like(sxx_ref)

    _accumulate(x_ref[...], rt_ref[...].T, n_ref, sx_ref, sxx_ref)


def _accumulate(x, r, n_ref, sx_ref, sxx_ref=None):
    """Fold one (bn, d) point tile into the resident (bk, ...) stat tiles
    given its (bn, bk) responsibility tile — the op order of the sweep
    megakernels' stat fold (kernels/sweep.py), so both paths add the same
    floats."""
    n_ref[...] += jnp.sum(r, axis=0, keepdims=True)
    sx_ref[...] += jnp.dot(r.T, x, preferred_element_type=jnp.float32)
    if sxx_ref is not None:
        # masked points per cluster: (bk, bn, d), then batched x^T x on
        # the MXU
        xw = r.T[:, :, None] * x[None, :, :]         # (bk, bn, d)
        sxx_ref[...] += jax.lax.dot_general(
            xw.transpose(0, 2, 1),
            jnp.broadcast_to(x, (r.shape[1],) + x.shape),
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)      # (bk, d, d)


def _tile_resp(lab_ref, sub_ref, val_ref, j, bk: int) -> jax.Array:
    """(bn, bk) one-hot over segments s = 2*label + sublabel, in VMEM,
    from the (bn, 1) label columns."""
    seg = lab_ref[...] * 2 + sub_ref[...]            # (bn, 1)
    col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], bk), 1)
    return (seg == col).astype(jnp.float32) * val_ref[...]


def _suffstats_labels_kernel(x_ref, lab_ref, sub_ref, val_ref,
                             n_ref, sx_ref, sxx_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        n_ref[...] = jnp.zeros_like(n_ref)
        sx_ref[...] = jnp.zeros_like(sx_ref)
        sxx_ref[...] = jnp.zeros_like(sxx_ref)

    r = _tile_resp(lab_ref, sub_ref, val_ref, pl.program_id(0),
                   n_ref.shape[1])
    _accumulate(x_ref[...], r, n_ref, sx_ref, sxx_ref)


def _moments_labels_kernel(x_ref, lab_ref, sub_ref, val_ref, n_ref, sx_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        n_ref[...] = jnp.zeros_like(n_ref)
        sx_ref[...] = jnp.zeros_like(sx_ref)

    r = _tile_resp(lab_ref, sub_ref, val_ref, pl.program_id(0),
                   n_ref.shape[1])
    _accumulate(x_ref[...], r, n_ref, sx_ref)


def _pad_points(arrs, bn: int):
    n = arrs[0].shape[0]
    pn = (-n) % bn
    if not pn:
        return arrs
    out = []
    for a in arrs:
        widths = [(0, pn)] + [(0, 0)] * (a.ndim - 1)
        out.append(jnp.pad(a, widths))
    return out


def _label_cols(labels, sublabels, valid, bn: int):
    """Per-point label vectors as (N, 1) columns in (bn, 1) blocks."""
    arrs = _pad_points((labels.astype(jnp.int32), sublabels.astype(jnp.int32),
                        jnp.asarray(valid, jnp.float32)), bn)
    spec = pl.BlockSpec((bn, 1), lambda j, i: (i, 0))
    return [a[:, None] for a in arrs], [spec] * 3


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def suffstats(x: jax.Array, resp: jax.Array, *, bn: int = 128, bk: int = 8,
              interpret: bool = False
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (N, d); resp: (N, K) -> (n (K,), sx (K, d), sxx (K, d, d))."""
    n_pts, d = x.shape
    if d > MAX_KERNEL_D:                 # documented VMEM guard: jnp path
        return ref.suffstats(x, resp)
    k = resp.shape[1]
    bn = min(bn, n_pts) or 1
    bk = min(bk, k) or 1
    pn, pk = (-n_pts) % bn, (-k) % bk
    if pn:
        x = jnp.pad(x, ((0, pn), (0, 0)))
        resp = jnp.pad(resp, ((0, pn), (0, 0)))
    if pk:
        resp = jnp.pad(resp, ((0, 0), (0, pk)))
    gk, gn = resp.shape[1] // bk, x.shape[0] // bn

    n_out, sx, sxx = pl.pallas_call(
        _suffstats_kernel,
        grid=(gk, gn),                       # N innermost: accumulation
        in_specs=[
            pl.BlockSpec((bn, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bk, bn), lambda j, i: (j, i)),
        ],
        out_specs=_stat_specs(bk, d, True),
        out_shape=_stat_shapes(resp.shape[1], bk, d, True),
        interpret=interpret,
    )(x, resp.T)
    return n_out.reshape(-1)[:k], sx[:k], sxx[:k]


def _stat_specs(bk: int, d: int, second: bool):
    # counts leave as (S/bk, 1, bk) rows: whole last two block dims
    specs = [pl.BlockSpec((None, 1, bk), lambda j, i: (j, 0, 0)),
             pl.BlockSpec((bk, d), lambda j, i: (j, 0))]
    if second:
        specs.append(pl.BlockSpec((bk, d, d), lambda j, i: (j, 0, 0)))
    return specs


def _stat_shapes(s: int, bk: int, d: int, second: bool):
    shapes = [jax.ShapeDtypeStruct((s // bk, 1, bk), jnp.float32),
              jax.ShapeDtypeStruct((s, d), jnp.float32)]
    if second:
        shapes.append(jax.ShapeDtypeStruct((s, d, d), jnp.float32))
    return shapes


@functools.partial(jax.jit,
                   static_argnames=("k", "bn", "bk", "interpret"))
def suffstats_labels(x: jax.Array, labels: jax.Array, sublabels: jax.Array,
                     valid: jax.Array, k: int, *, bn: int = 128,
                     bk: int = 8, interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Label-indexed sub-cluster stats; one-hot never leaves VMEM.

    x: (N, d); labels/sublabels: (N,) int32; valid: (N,) bool ->
    (n (k, 2), sx (k, 2, d), sxx (k, 2, d, d)).
    """
    n_pts, d = x.shape
    assert d <= MAX_KERNEL_D, (
        f"suffstats_labels: d={d} exceeds the VMEM budget "
        f"(MAX_KERNEL_D={MAX_KERNEL_D}); use the family's segment-sum "
        "reference path (kernels/ops.py guards this)")
    s = 2 * k
    bn = min(bn, n_pts) or 1
    bk = min(bk, s)
    (x,) = _pad_points((x,), bn)
    cols, col_specs = _label_cols(labels, sublabels, valid, bn)
    ps = (-s) % bk
    gk, gn = (s + ps) // bk, x.shape[0] // bn

    n2, sx2, sxx2 = pl.pallas_call(
        _suffstats_labels_kernel,
        grid=(gk, gn),
        in_specs=[pl.BlockSpec((bn, d), lambda j, i: (i, 0)), *col_specs],
        out_specs=_stat_specs(bk, d, True),
        out_shape=_stat_shapes(s + ps, bk, d, True),
        interpret=interpret,
    )(x, *cols)
    return (n2.reshape(-1)[:s].reshape(k, 2), sx2[:s].reshape(k, 2, d),
            sxx2[:s].reshape(k, 2, d, d))


@functools.partial(jax.jit,
                   static_argnames=("k", "bn", "bk", "interpret"))
def moments_labels(feats: jax.Array, labels: jax.Array,
                   sublabels: jax.Array, valid: jax.Array, k: int, *,
                   bn: int = 128, bk: int = 8, interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Label-indexed first moments for the feature-separable families.

    feats: (N, d') per-point features (x, or [x, x^2] stacked) ->
    (n (k, 2), sf (k, 2, d')).
    """
    n_pts, dp = feats.shape
    assert dp <= 2 * MAX_KERNEL_D, (
        f"moments_labels: d'={dp} exceeds the VMEM budget; use the "
        "family's segment-sum reference path (kernels/ops.py guards this)")
    s = 2 * k
    bn = min(bn, n_pts) or 1
    bk = min(bk, s)
    (feats,) = _pad_points((feats,), bn)
    cols, col_specs = _label_cols(labels, sublabels, valid, bn)
    ps = (-s) % bk
    gk, gn = (s + ps) // bk, feats.shape[0] // bn

    n2, sf2 = pl.pallas_call(
        _moments_labels_kernel,
        grid=(gk, gn),
        in_specs=[pl.BlockSpec((bn, dp), lambda j, i: (i, 0)), *col_specs],
        out_specs=_stat_specs(bk, dp, False),
        out_shape=_stat_shapes(s + ps, bk, dp, False),
        interpret=interpret,
    )(feats, *cols)
    return n2.reshape(-1)[:s].reshape(k, 2), sf2[:s].reshape(k, 2, dp)
