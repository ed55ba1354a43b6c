"""Counter-based threefry2x32 RNG usable both inside Pallas kernels and in
pure-jnp reference code.

This is the TPU adaptation of the paper's CURAND usage: random bits are
produced on the fly from (key, counter) with pure uint32 VPU arithmetic —
no RNG state ever touches HBM (DESIGN.md §2).  Streams are indexed by
(seed, global_chain_index, step, draw), so results are *identical* under any
chain blocking/sharding — the kernel and the reference oracle agree exactly.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = np.uint32(0x1BD11BDA)


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """Standard 20-round threefry2x32. All args uint32 arrays (broadcastable).

    Returns two uint32 arrays of the broadcast shape.
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    x0 = jnp.asarray(x0, jnp.uint32)
    x1 = jnp.asarray(x1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for block in range(5):
        for i in range(4):
            x0 = x0 + x1
            x1 = _rotl(x1, _ROT[(block * 4 + i) % 8])
            x1 = x1 ^ x0
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def uniform_from_bits(bits):
    """uint32 -> float32 uniform in [0, 1) with 24-bit mantissa usage.

    The cast goes through int32 (the Pallas TPU lowering has no
    uint32 -> float32 cast); ``bits >> 8`` is below 2**24, so both casts
    are exact and the result is unchanged.
    """
    top24 = (bits >> np.uint32(8)).astype(jnp.int32)
    return top24.astype(jnp.float32) * np.float32(1.0 / (1 << 24))


def draws3(seed, chain_idx, step):
    """The paper's three uniforms per Metropolis step + one spare.

    chain_idx: uint32 array (any shape); step: scalar uint32.
    Returns (u_coord_bits, u_value, u_accept) — the coordinate draw is
    returned as raw bits so the caller can mod by ``dim`` without bias games.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    step = jnp.asarray(step, jnp.uint32)
    c = jnp.asarray(chain_idx, jnp.uint32)
    r0, r1 = threefry2x32(seed, step * np.uint32(2), c, jnp.zeros_like(c))
    r2, _ = threefry2x32(seed, step * np.uint32(2) + np.uint32(1), c, jnp.ones_like(c))
    return r0, uniform_from_bits(r1), uniform_from_bits(r2)
