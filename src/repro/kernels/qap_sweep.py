"""Pallas TPU kernel: fused pairwise-exchange QAP sweep (permutation family).

The combinatorial counterpart of metropolis_sweep.py, after Paul's GPU SA
for the QAP (arXiv 1208.2675): one kernel invocation advances a block of
``blk`` chains — each an ``int32`` permutation ``p`` of ``n`` locations —
by ``n_steps`` pairwise-exchange Metropolis moves at fixed temperature,
entirely in VMEM.  Each proposal swaps the locations of two facilities
``i, j`` and evaluates the cost change in **O(n)** (the delta trick), not
O(n^2); the accept test, RNG and per-block SMEM control layout are shared
with the continuous kernel:

  RNG           : the same counter-based threefry2x32 draws3 stream,
                  indexed by (request seed, global chain index, step) — so
                  QAP trajectories are placement/preemption/migration
                  invariant exactly like continuous ones.
  controls      : per-block SMEM arrays (T, seed, step0, chain_base, live)
                  indexed by ``program_id`` — heterogeneous serving slots
                  in one launch, ``live`` masking dead macro-tick blocks.
  constants     : per-request flow/distance matrices enter as *per-block
                  VMEM operands* — packed ``(n_blocks * n, n)`` so each
                  block reads its own instance — which keeps the compiled
                  program independent of which QAP instances occupy the
                  batch: one lowering per ``(n, n_steps, blk)``.

Exactness contract
------------------
Registered instances carry small-integer matrices, so every product and
partial sum below is an integer far below 2**24: float32 arithmetic on
them is *exact* and order-independent.  The delta-carried ``fx`` therefore
equals a from-scratch ``qap_full_cost`` **bitwise**, and the pure-jnp
oracle (`ref.qap_sweep_ref`, built on the same shared step math) matches
the Pallas lowering bitwise — the property the serving engine's
bit-exactness oracle stands on (tests/test_qap.py).

Gathers are one-hot row selects (``(1, n) @ (n, n)`` products) and
static loops over the ``n`` locations on 2-D ``(blk, n)`` tiles: sums of a
single non-zero term, exact regardless of order.  No rank-3 one-hot is
built — the Pallas TPU compiler aborts on one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import rng
from repro.kernels.metropolis_sweep import _per_block


def _onehot(v, n: int):
    """``(B, 1)`` int column -> ``(B, n)`` float32 one-hot rows."""
    return (lax.broadcasted_iota(v.dtype, (v.shape[0], n), 1) == v
            ).astype(jnp.float32)


def _row(M, v):
    """One-hot row select: ``_row(M, onehot(i))[b, k] = M[i, k]``.

    ``v`` is ``(B, n)``.  A shared ``(n, n)`` operand (the kernel's) takes
    one ``(B, n) @ (n, n)`` matmul; a per-chain ``(B, n, n)`` operand (the
    oracle's mixed-instance batches) one ``(1, n) @ (n, n)`` product per
    chain.  Either way each entry is a sum of one non-zero term, exact in
    any order and at any matmul precision for the small-integer instances.
    """
    if M.ndim == 2:
        return v @ M
    return (v[:, None, :] @ M)[:, 0, :]


def _gather(R, p):
    """``_gather(R, p)[b, k] = R[b, p[b, k]]`` for ``(B, n)`` rows ``R``.

    A static loop over the ``n`` locations on 2-D ``(B, n)`` tiles (a
    rank-3 ``(B, n, n)`` one-hot aborts the Pallas TPU compiler): each
    output element picks one term and adds exact zeros.
    """
    n = p.shape[-1]
    out = jnp.zeros(p.shape, jnp.float32)
    for loc in range(n):
        out = out + jnp.where(p == loc, R[:, loc:loc + 1], 0.0)
    return out


def qap_full_cost(p, F, D):
    """Full QAP cost ``sum_{u,v} F[u,v] * D[p[u],p[v]]`` per chain.

    Args:
      p: (B, n) int32 permutations.
      F, D: (n, n) — or (B, n, n) per-chain — float32 integer-valued
        matrices.

    Returns (B, 1) float32 costs — exact for integer data below 2**24.
    """
    n = p.shape[-1]
    cost = jnp.zeros((p.shape[0], 1), jnp.float32)
    for u in range(n):
        # Row u of D[p, p]: D[p[u], :] through the one-hot row select,
        # then its columns gathered at p.
        dp_u = _gather(_row(D, _onehot(p[:, u:u + 1], n)), p)
        f_u = F[u:u + 1, :] if F.ndim == 2 else F[:, u, :]
        cost = cost + jnp.sum(f_u * dp_u, axis=-1, keepdims=True)
    return cost


def qap_swap_sweep(p, fx, F, D, T, seed, cidx, step0, *, n_steps: int,
                   live=None):
    """``n_steps`` pairwise-exchange Metropolis moves, delta-evaluated.

    The *shared* step recurrence: both the Pallas kernel (per block,
    (n, n) operands, SMEM scalars) and the pure-jnp oracle (whole batch,
    per-chain columns/operands) call exactly this function, so the two
    paths agree bitwise by construction for integer-valued data.

    Per step, from one ``rng.draws3`` triple: facility ``i`` from the raw
    bits (mod n), facility ``j`` from the value uniform (floor(u * n)),
    and the accept uniform.  ``i == j`` proposes the identity (delta is
    exactly 0.0, always accepted, state unchanged).  The delta for
    swapping the locations ``a = p[i]``, ``b = p[j]`` is the general
    (asymmetric-F/D) O(n) form:

      sum_{k != i,j} (F[i,k]-F[j,k]) (D[b,p[k]]-D[a,p[k]])
                   + (F[k,i]-F[k,j]) (D[p[k],b]-D[p[k],a])
      + (F[i,i]-F[j,j]) (D[b,b]-D[a,a]) + (F[i,j]-F[j,i]) (D[b,a]-D[a,b])

    Args:
      p: (B, n) int32 permutations; fx: (B, 1) float32 current costs.
      F, D: (n, n) or (B, n, n) float32 operands.
      T: temperature — scalar or (B, 1) column.
      seed / cidx / step0: RNG stream coordinates (uint32; scalar or
        (B, 1)), identical indexing to the continuous kernel.
      live: optional mask (scalar bool or (B, 1)); dead rows pass through
        bit-exactly (no accepted moves, no stream consumed — draws are
        stateless).

    Returns (p, fx) after ``n_steps`` moves.
    """
    n = p.shape[-1]
    locs = jnp.arange(n, dtype=p.dtype)

    def body(s, carry):
        p, fx = carry
        rbits, uval, uacc = rng.draws3(seed, cidx,
                                       (step0 + s).astype(jnp.uint32))
        i_fac = (rbits % jnp.uint32(n)).astype(p.dtype)          # (B, 1)
        j_fac = jnp.minimum((uval * n).astype(p.dtype), n - 1)   # (B, 1)
        ei = locs[None, :] == i_fac                              # (B, n)
        ej = locs[None, :] == j_fac
        eif = ei.astype(jnp.float32)
        ejf = ej.astype(jnp.float32)
        a = jnp.sum(jnp.where(ei, p, 0), axis=-1, keepdims=True)  # p[i]
        b = jnp.sum(jnp.where(ej, p, 0), axis=-1, keepdims=True)  # p[j]
        laf = (locs[None, :] == a).astype(jnp.float32)
        lbf = (locs[None, :] == b).astype(jnp.float32)

        FT = jnp.swapaxes(F, -1, -2)
        DT = jnp.swapaxes(D, -1, -2)
        Fi, Fj = _row(F, eif), _row(F, ejf)        # F[i,:], F[j,:]
        FiT, FjT = _row(FT, eif), _row(FT, ejf)    # F[:,i], F[:,j]
        Da, Db = _row(D, laf), _row(D, lbf)        # D[a,:], D[b,:]
        DaT, DbT = _row(DT, laf), _row(DT, lbf)    # D[:,a], D[:,b]

        kmask = (1.0 - eif) * (1.0 - ejf)                 # k not in {i, j}
        # Rows gathered at p[k]: _gather(R, p)[k] = R[p[k]].
        t1 = jnp.sum((Fi - Fj) * (_gather(Db, p) - _gather(Da, p)) * kmask,
                     axis=-1, keepdims=True)
        t2 = jnp.sum((FiT - FjT) * (_gather(DbT, p) - _gather(DaT, p))
                     * kmask, axis=-1, keepdims=True)

        def pick(R, v):
            return jnp.sum(R * v, axis=-1, keepdims=True)

        diag = (pick(Fi, eif) - pick(Fj, ejf)) \
            * (pick(Db, lbf) - pick(Da, laf))
        cross = (pick(Fi, ejf) - pick(Fj, eif)) \
            * (pick(Db, laf) - pick(Da, lbf))
        delta = t1 + t2 + diag + cross

        acc = uacc <= jnp.exp(jnp.clip(-delta / T, -80.0, 80.0))
        if live is not None:
            acc = acc & live
        p_new = jnp.where(ei, b, jnp.where(ej, a, p))
        p = jnp.where(acc, p_new, p)
        fx = jnp.where(acc, fx + delta, fx)
        return p, fx

    return lax.fori_loop(0, n_steps, body, (p, fx))


def _qap_kernel(T_ref, seed_ref, step0_ref, base_ref, live_ref,
                p_ref, F_ref, D_ref, po_ref, fo_ref, *, n_steps: int,
                blk: int):
    """One grid step: sweep one (blk, n) block on its own instance."""
    pid = pl.program_id(0)
    T = T_ref[pid]
    seed = seed_ref[pid]
    step0 = step0_ref[pid]
    live = live_ref[pid] != 0
    cidx = (base_ref[pid]
            + lax.broadcasted_iota(jnp.int32, (blk, 1), 0).astype(jnp.uint32))
    p = p_ref[...]
    F = F_ref[...]                  # (n, n): the squeezed instance block
    D = D_ref[...]
    # Initial cost from scratch — exact (integer-valued f32), so the carry
    # that leaves this kernel bitwise equals a host full evaluation.
    fx = qap_full_cost(p, F, D)
    p, fx = qap_swap_sweep(p, fx, F, D, T, seed, cidx, step0,
                           n_steps=n_steps, live=live)
    po_ref[...] = p
    fo_ref[...] = fx


def qap_sweep_pallas(p, F_blocks, D_blocks, T, seed, step0, *,
                     n_steps: int, blk: int = 256, interpret: bool = False,
                     chain_base=None, live=None):
    """Run an N-step QAP swap sweep for all chains.

    Args:
      p: (chains, n) int32 permutation states; ``chains`` must be a
        multiple of ``blk`` (the serving engine always packs whole slots).
      F_blocks, D_blocks: per-block instance operands — ``(n, n)`` (one
        instance for every block) or packed ``(n_blocks * n, n)`` (block
        ``b`` reads rows ``[b*n, (b+1)*n)``); float32, integer-valued.
      T, seed, step0: per-block SMEM controls, scalar or (chains//blk,)
        — same semantics as metropolis_sweep_pallas.
      chain_base: optional per-block global chain-index base (uint32);
        defaults to ``block * blk``.
      live: optional per-block level cursor (bool/int32); dead blocks pass
        through bit-exactly (macro-tick fusion).

    Returns (p_out, f_out): (chains, n) int32 and (chains,) float32.
    """
    chains, n = p.shape
    if chains % blk:
        raise ValueError(
            f"chains={chains} must be a multiple of blk={blk} for the QAP "
            "sweep (the engine packs whole slots)")
    grid = (chains // blk,)
    n_blocks = grid[0]

    def pack(M, name):
        M = jnp.asarray(M, jnp.float32)
        if M.shape == (n, n):
            M = jnp.tile(M, (n_blocks, 1))
        if M.shape != (n_blocks * n, n):
            raise ValueError(
                f"{name} must be (n, n) or (n_blocks*n, n) = "
                f"({n_blocks * n}, {n}); got {M.shape}")
        # (n_blocks, n, n) with a squeezed leading block dim: the block's
        # last two dims then equal the array's, as the TPU lowering needs.
        return M.reshape(n_blocks, n, n)

    Fb = pack(F_blocks, "F_blocks")
    Db = pack(D_blocks, "D_blocks")
    t_arr = _per_block(T, n_blocks, jnp.float32, "T")
    seed_arr = _per_block(seed, n_blocks, jnp.uint32, "seed")
    step0_arr = _per_block(step0, n_blocks, jnp.uint32, "step0")
    if chain_base is None:
        base_arr = (jnp.arange(n_blocks, dtype=jnp.uint32)
                    * jnp.uint32(blk))
    else:
        base_arr = _per_block(chain_base, n_blocks, jnp.uint32, "chain_base")
    live_arr = (_per_block(1, n_blocks, jnp.int32, "live") if live is None
                else _per_block(live, n_blocks, jnp.int32, "live"))

    kernel = functools.partial(_qap_kernel, n_steps=n_steps, blk=blk)
    p_out, f_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=(
            [pl.BlockSpec(memory_space=pltpu.SMEM)] * 5
            + [pl.BlockSpec((blk, n), lambda i: (i, 0)),
               pl.BlockSpec((None, n, n), lambda i: (i, 0, 0)),
               pl.BlockSpec((None, n, n), lambda i: (i, 0, 0))]),
        out_specs=[
            pl.BlockSpec((blk, n), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((chains, n), p.dtype),
            jax.ShapeDtypeStruct((chains, 1), jnp.float32),
        ],
        interpret=interpret,
        name=f"qap_sweep_n{n}",
    )(t_arr, seed_arr, step0_arr, base_arr, live_arr, p, Fb, Db)
    return p_out, f_out[:, 0]
