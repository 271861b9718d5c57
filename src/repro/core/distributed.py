"""Distribution plumbing for the DPMM sampler.

Mirrors the paper's §4.3: points, labels, and sub-labels live on their
owning shard ('the data never moves'); per-cluster parameters and
sufficient statistics are replicated, with a single psum per suff-stat
pass. Works on any mesh whose data axes partition N; the ``model`` axis
(when present and ``shard_features`` is on) shards the feature dimension of
the multinomial likelihood (DESIGN §2).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def make_data_mesh(num_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over all (or the first n) local devices, axis 'data'."""
    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return Mesh(np.array(devs), axis_names=("data",))


def data_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    """Axes that partition points: every axis except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_data_shards(mesh: Mesh) -> int:
    """Number of shards the data axes partition points into."""
    return int(np.prod([mesh.shape[a] for a in data_axes_of(mesh)],
                       dtype=np.int64))


def tile_plan(n: int, n_shards: int, tile_size: Optional[int]
              ) -> Tuple[int, Sequence[Tuple[int, int]]]:
    """Per-shard tile layout for the streamed data plane.

    Returns ``(n_local, [(offset, length), ...])``: every data shard holds
    exactly ``n_local = ceil(n / n_shards)`` rows (the same padded layout
    ``shard_points`` produces for the resident plane, so global point
    indices — and therefore chains — match bitwise across planes), cut
    into tiles at STATS_BLOCK-aligned offsets. Alignment keeps the
    suff-stat block fold's float addition order identical for every tile
    size (core/gibbs.py); only the shard's ragged tail tile may be
    non-multiple. ``tile_size`` is rounded up to the alignment; ``None``
    picks a default sized for streaming (64 blocks).
    """
    from repro.core.gibbs import STATS_BLOCK
    n_local = -(-n // n_shards)
    if tile_size is None:
        tile_size = 64 * STATS_BLOCK
    tile = -(-tile_size // STATS_BLOCK) * STATS_BLOCK
    tile = min(tile, n_local)
    tiles = [(off, min(tile, n_local - off))
             for off in range(0, n_local, tile)]
    return n_local, tiles


def pad_to_multiple(x: np.ndarray, multiple: int):
    """Pad axis 0 to a multiple; returns (padded, valid_mask)."""
    n = x.shape[0]
    target = int(math.ceil(n / multiple) * multiple)
    valid = np.zeros((target,), np.float32)
    valid[:n] = 1.0
    if target == n:
        return x, valid
    pad = np.zeros((target - n,) + x.shape[1:], x.dtype)
    return np.concatenate([x, pad], axis=0), valid


def shard_points(mesh: Mesh, x: np.ndarray, shard_features: bool = False):
    """Place (N, d) points on the mesh; returns (x_sharded, valid_sharded)."""
    axes = data_axes_of(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    x_p, valid = pad_to_multiple(np.asarray(x), n_shards)
    feat = "model" if (shard_features and "model" in mesh.axis_names) else None
    xs = jax.device_put(x_p, NamedSharding(mesh, P(axes, feat)))
    vs = jax.device_put(valid, NamedSharding(mesh, P(axes)))
    return xs, vs


def replicated(mesh: Mesh, tree):
    return jax.device_put(tree, NamedSharding(mesh, P()))
