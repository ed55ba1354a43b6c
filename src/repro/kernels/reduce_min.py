"""Pallas TPU kernel: block-tree (min, argmin) reduction.

The paper's V1/V2 champion selection is a Thrust ``reduceMin`` over the
per-chain objective values (shared-memory partial reductions per block,
then a host-side combine).  TPU adaptation: a grid of chain blocks, each
reducing its (1, blk) VMEM tile to a per-block (min, argmin) pair on the
VPU; the tiny (n_blocks,) tail is combined with a plain ``jnp.argmin``
(the analogue of Thrust's final pass, but staying on-device).

Tie-breaking matches ``jnp.argmin``: the first (lowest-index) minimum wins
within a block and across blocks, so the kernel is bit-identical to the
oracle (tests/test_kernels_pallas.py sweeps shapes/dtypes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


#: Each block writes its (min, argmin) broadcast over one whole (8, 128)
#: f32 tile: the TPU lowering needs output blocks whose last two dims
#: divide by (8, 128), so a (1, 1) block per grid step cannot lower.
_TILE = (8, 128)


def _argmin_kernel(f_ref, m_ref, i_ref, *, blk: int):
    pid = pl.program_id(0)
    f = f_ref[...]                                    # (1, blk)
    idx = lax.broadcasted_iota(jnp.int32, (1, blk), 1)
    m = jnp.min(f, axis=1, keepdims=True)             # (1, 1)
    # first index attaining the block minimum
    i = jnp.min(jnp.where(f == m, idx, blk), axis=1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m, _TILE)
    i_ref[...] = jnp.broadcast_to(pid * blk + i, _TILE)


def block_argmin_pallas(f, *, blk: int = 1024, interpret: bool = False):
    """Per-block (min, argmin) of a 1-D value vector.

    Returns (mins (n_blocks,), idxs (n_blocks,)); combine with
    :func:`argmin_reduce` (or any tail reduce).
    """
    (n,) = f.shape
    if n % blk:
        raise ValueError(f"n={n} must be a multiple of blk={blk}")
    grid = (n // blk,)
    rows, lanes = _TILE
    tile = pl.BlockSpec(_TILE, lambda i: (i, 0))
    mins, idxs = pl.pallas_call(
        functools.partial(_argmin_kernel, blk=blk),
        grid=grid,
        in_specs=[pl.BlockSpec((1, blk), lambda i: (0, i))],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((grid[0] * rows, lanes), f.dtype),
                   jax.ShapeDtypeStruct((grid[0] * rows, lanes), jnp.int32)],
        interpret=interpret,
        name="block_argmin",
    )(f.reshape(1, n))
    return mins[::rows, 0], idxs[::rows, 0]


def argmin_reduce(f, *, blk: int = 1024, use_pallas: bool = False,
                  interpret: bool = False):
    """(min_value, argmin_index) of ``f`` — the paper's reduceMin.

    With ``use_pallas`` the per-block stage runs as the TPU kernel;
    otherwise pure jnp (identical result).
    """
    (n,) = f.shape
    if use_pallas and n % blk == 0 and n >= blk:
        mins, idxs = block_argmin_pallas(f, blk=blk, interpret=interpret)
        j = jnp.argmin(mins)            # ties: first block wins, as jnp
        return mins[j], idxs[j]
    i = jnp.argmin(f)
    return f[i], i.astype(jnp.int32)
