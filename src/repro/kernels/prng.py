"""Counter-based Threefry-2x32 — one Gumbel formula for both sweep paths.

The fused assignment kernels (kernels/assign.py) cannot call
``jax.random.gumbel(fold_in(key, i), (k,))`` per point: typed-key plumbing
does not exist inside a Pallas kernel body, and the reference sweep must
produce *bitwise-identical* noise so fused and reference paths sample the
same chain. So per-(point, cluster) noise is defined here once, as a pure
counter-based function of ``(key, global_index, cluster_index)``:

    bits = threefry2x32(key, counter=(global_index, cluster_index))
    u    = (bits >> 8 + 0.5) * 2^-24            # (0, 1) strictly
    g    = -log(-log(u))                        # standard Gumbel

``threefry2x32`` is the standard 20-round Threefry-2x32 block cipher — the
same PRNG JAX's default implementation uses — written in plain ``jnp``
uint32 ops (add/xor/rotate), so the identical expression traces inside a
Pallas kernel body (interpret mode *is* jnp; on TPU it lowers to VPU
integer ops) and in the jnp reference sweep. Keying per *global* point
index preserves the sharding-invariance property (DESIGN §2, assumption 3):
chains are bitwise identical under any data sharding.

Everything broadcasts: pass ``c0 = gidx[:, None]`` and ``c1`` a cluster
iota to draw an (N, K) tile/matrix in one call.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# Threefry-2x32 rotation schedule (Salmon et al. 2011, Random123).
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA  # key-schedule parity constant


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0: jax.Array, k1: jax.Array, c0: jax.Array,
                 c1: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """20-round Threefry-2x32 of counter (c0, c1) under key (k0, k1).

    All inputs uint32 (arrays broadcast); returns two uint32 blocks.
    Matches ``jax._src.prng.threefry_2x32`` bit-for-bit.
    """
    k0 = k0.astype(jnp.uint32)
    k1 = k1.astype(jnp.uint32)
    x0 = c0.astype(jnp.uint32) + k0
    x1 = c1.astype(jnp.uint32) + k1
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def uniform01(bits: jax.Array) -> jax.Array:
    """uint32 bits -> f32 uniform strictly inside (0, 1).

    Uses the top 24 bits at bin centers: u = (bits>>8 + 0.5) / 2^24, so
    u in [2^-25, 1 - 2^-25] and log(u), log(-log(u)) are always finite.
    """
    # 24-bit values convert exactly through int32 (Mosaic has no
    # uint32 -> f32 conversion; the result is the same float either way)
    top = (bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    return (top + 0.5) * jnp.float32(1.0 / (1 << 24))


def gumbel(key_data: jax.Array, c0: jax.Array, c1: jax.Array) -> jax.Array:
    """Standard Gumbel noise keyed by counters (c0, c1); broadcasts.

    ``key_data``: (2,) uint32 raw key words (``jax.random.key_data``), or
    a kernel's SMEM ref holding them (indexing reads the two scalars).
    """
    b0, _ = threefry2x32(key_data[0], key_data[1], c0, c1)
    return -jnp.log(-jnp.log(uniform01(b0)))


def key_words(key: jax.Array) -> jax.Array:
    """Typed PRNG key -> (2,) uint32 words for the counter-based draws."""
    data = jax.random.key_data(key).reshape(-1)
    return data[:2].astype(jnp.uint32)
