"""Pure-jnp oracle for the Metropolis-sweep kernel.

Runs the Pallas kernel's own sweep body (``metropolis_sweep.sweep_chains``:
same RNG counters, same accumulator math) vectorized over all chains at
once with no blocking, as plain XLA.  Only the accumulator init's lane
sum differs (``objective_math.lane_sum_tree``: XLA:CPU rounds a fused
row reduction differently from one program shape to another).  Because
the RNG is counter-based on the global chain index, the kernel's
chain-block decomposition does not change random streams, so kernel and
oracle agree to float tolerance.

For the multi-tenant serving engine the control inputs generalize from
scalars to per-chain arrays: ``kid``, ``T``, ``seed`` and ``step0`` may each
be a scalar or a ``(chains,)`` array, and ``cidx`` optionally overrides the
global chain indices — the per-chain analogue of the kernel's per-block
SMEM arrays (a serving slot's chains all share one entry).

Like the kernel, the objective id ``kid`` is a *runtime* input when passed
as an array or traced value (dispatched with branchless ``jnp.where``
chains — objective_math ``*_rt``), so one compiled oracle serves every
registry objective at a fixed ``(dim, n_steps, variant)`` and
mixed-objective batches are legal.  A concrete Python-int ``kid`` compiles
the single objective branch instead (1x objective math for batch callers;
both paths are bit-exact against each other by construction).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import metropolis_sweep as ms
from repro.kernels import objective_math as om


def _col(v, chains: int, dtype):
    """Scalar or (chains,) input -> (chains, 1) column."""
    a = jnp.asarray(v, dtype).reshape(-1)
    if a.shape[0] == 1:
        a = jnp.broadcast_to(a, (chains,))
    return a[:, None]


def metropolis_sweep_ref(x, T, seed, step0, *, kid, n_steps: int,
                         variant: str = "delta", cidx=None, live=None):
    ms._validate_kid(kid)
    # Concrete scalar kid -> single-branch specialization (1x objective
    # math, one jit cache entry per objective — the pre-runtime behavior);
    # array/traced kid -> runtime jnp.where dispatch, one entry total.
    if isinstance(kid, (int, np.integer)):
        return _metropolis_sweep_ref_static(
            x, T, seed, step0, kid=int(kid), n_steps=n_steps,
            variant=variant, cidx=cidx, live=live)
    return _metropolis_sweep_ref(x, T, seed, step0, kid=kid, n_steps=n_steps,
                                 variant=variant, cidx=cidx, live=live)


@partial(jax.jit, static_argnames=("kid", "n_steps", "variant"))
def _metropolis_sweep_ref_static(x, T, seed, step0, *, kid: int,
                                 n_steps: int, variant: str = "delta",
                                 cidx=None, live=None):
    lo, hi = om.BOX[kid]
    return _sweep_ref_body(x, T, seed, step0, kid, np.float32(lo),
                           np.float32(hi), ms.STATIC_FNS, n_steps, variant,
                           cidx, live)


@partial(jax.jit, static_argnames=("n_steps", "variant"))
def _metropolis_sweep_ref(x, T, seed, step0, *, kid, n_steps: int,
                          variant: str = "delta", cidx=None, live=None):
    kid = _col(kid, x.shape[0], jnp.int32)
    lo, hi = om.box_rt(kid, dtype=x.dtype)  # (chains, 1) box bounds
    return _sweep_ref_body(x, T, seed, step0, kid, lo, hi, ms.RUNTIME_FNS,
                           n_steps, variant, cidx, live)


def _sweep_ref_body(x, T, seed, step0, kid, lo, hi, fns, n_steps, variant,
                    cidx, live=None):
    chains = x.shape[0]
    if cidx is None:
        cidx = jnp.arange(chains, dtype=jnp.uint32)[:, None]  # (chains, 1)
    else:
        cidx = _col(cidx, chains, jnp.uint32)
    # Per-chain level cursor (macro-tick serving): a dead chain's accepts
    # are all masked off so its state passes through bit-exactly — the
    # oracle-side mirror of the kernel's per-block ``live`` SMEM input.
    live = None if live is None else _col(live, chains, jnp.bool_)
    x, fx = ms.sweep_chains(
        x, _col(T, chains, x.dtype), _col(seed, chains, jnp.uint32), cidx,
        _col(step0, chains, jnp.uint32), kid=kid, lo=lo, hi=hi, fns=fns,
        n_steps=n_steps, variant=variant, live=live,
        reduce=om.lane_sum_tree)
    return x, fx[:, 0]


def qap_sweep_ref(p, F, D, T, seed, step0, *, n_steps: int, cidx=None,
                  live=None):
    """Pure-jnp oracle for the QAP pairwise-exchange sweep kernel.

    Runs the *shared* step recurrence (``qap_sweep.qap_swap_sweep``) over
    the whole batch at once, so it is bit-exact against the Pallas
    lowering by construction — the permutation-family analogue of
    ``metropolis_sweep_ref``.  ``F``/``D`` are ``(n, n)`` (one instance for
    every chain) or per-chain ``(chains, n, n)``; ``T``/``seed``/``step0``
    are scalars or ``(chains,)``; ``cidx`` optionally overrides the global
    chain indices and ``live`` is the per-chain macro-tick level cursor.

    Returns (p_out (chains, n) int32, f_out (chains,) float32).
    """
    return _qap_sweep_ref(p, F, D, T, seed, step0, n_steps=n_steps,
                          cidx=cidx, live=live)


@partial(jax.jit, static_argnames=("n_steps",))
def _qap_sweep_ref(p, F, D, T, seed, step0, *, n_steps: int, cidx=None,
                   live=None):
    from repro.kernels.qap_sweep import qap_full_cost, qap_swap_sweep
    chains = p.shape[0]
    if cidx is None:
        cidx = jnp.arange(chains, dtype=jnp.uint32)[:, None]
    else:
        cidx = _col(cidx, chains, jnp.uint32)
    seed = _col(seed, chains, jnp.uint32)
    step0 = _col(step0, chains, jnp.uint32)
    T = _col(T, chains, jnp.float32)
    live = None if live is None else _col(live, chains, jnp.bool_)
    F = jnp.asarray(F, jnp.float32)
    D = jnp.asarray(D, jnp.float32)
    fx = qap_full_cost(p, F, D)
    p, fx = qap_swap_sweep(p, fx, F, D, T, seed, cidx, step0,
                           n_steps=n_steps, live=live)
    return p, fx[:, 0]
