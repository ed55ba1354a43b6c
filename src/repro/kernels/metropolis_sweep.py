"""Pallas TPU kernel: fused Metropolis sweep (the paper's Listing 2/4 body).

One kernel invocation advances a block of ``blk`` chains by ``n_steps``
Metropolis iterations at fixed temperature, entirely in VMEM:

  HBM traffic   : one read of the (blk, dim) state block + one write, per
                  *sweep* (N steps) — the CUDA version's design goal
                  ("no global-memory round trips inside the chain") mapped
                  to the TPU memory hierarchy.
  RNG           : counter-based threefry2x32 on the VPU (see rng.py); the
                  TPU analogue of per-thread CURAND state.
  accept/reject : branchless masked selects — no divergence on TPU.

Variants
--------
``full``  : paper-faithful — every proposal evaluates the objective over all
            ``dim`` coordinates (O(dim) transcendentals per step).
``delta`` : beyond-paper — sum/product accumulators updated in O(1) per step
            (DESIGN.md §2); identical proposal/acceptance stream.

Multi-tenant serving (service/engine.py) drives *heterogeneous* chain-blocks
through one kernel launch: every SMEM control input (objective id,
temperature, RNG seed, step counter, global chain-index base) is a per-block
array indexed by ``program_id``, so each block — one serving *slot* —
anneals its own objective at its own temperature and draws from its own
request's random stream regardless of which slot it was packed into.
Scalar inputs broadcast to all blocks, which keeps the original single-job
call signature working unchanged.

Invariants
----------
* ``kid`` is a **runtime** input (per-block SMEM int32) whenever it is
  passed as an array or traced value — the serving engine's path: one
  compiled program serves every registry objective at a fixed
  ``(dim, n_steps, blk, variant)``, dispatching inside the kernel with
  branchless ``jnp.where`` chains (objective_math ``*_rt``).  Growing the
  objective registry therefore never triggers a recompile — the serving
  engine's compile-stability guarantee.  The runtime path evaluates all
  ``N_KIDS`` branches per proposal and selects one; a *concrete Python
  int* ``kid`` instead compiles the single branch (the pre-runtime
  specialization — batch/benchmark callers keep 1x objective math, at the
  old cost of one lowering per objective).
* Runtime dispatch is bit-exact versus the static-``kid`` lowering: each
  ``jnp.where`` branch is the identical floating-point expression, so the
  two paths interleave freely (tests compare them directly).
* One kernel invocation advances every chain by exactly ``n_steps``
  proposals at its block's (fixed) temperature — the serving engine's
  "one tick = one temperature level" contract bottoms out here.

Block shape: ``(blk, dim)``; ``blk`` is a multiple of 8 (sublanes), ``dim``
pads to the 128-lane VREG width. Chains are fully independent so the grid
over chain-blocks is embarrassingly parallel ("arbitrary dimension" in
Mosaic terms). A chain count that is not a multiple of ``blk`` is padded up
(and sliced back) rather than rejected; padded chains burn VPU lanes but
never perturb real chains' streams (counter-based RNG on the global index).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import objective_math as om
from repro.kernels import rng


def _accept_prob(f0, f1, T):
    return jnp.exp(jnp.clip(-(f1 - f0) / T, -80.0, 80.0))


def _step_draws(seed, cidx, step0, i):
    """Three uniforms for step i (paper Step 3): coord bits, value, accept."""
    return rng.draws3(seed, cidx, (step0 + i).astype(jnp.uint32))


#: Objective math per dispatch surface (objective_math): a concrete
#: Python-int ``kid`` traces one branch, a traced ``kid`` all of them.
STATIC_FNS = (om.init_acc, om.combine, om.term, om.full_eval)
RUNTIME_FNS = (om.init_acc_rt, om.combine_rt, om.term_rt, om.full_eval_rt)


def sweep_chains(x, T, seed, cidx, step0, *, kid, lo, hi, fns,
                 n_steps: int, variant: str, live=None,
                 reduce=om.lane_sum):
    """``n_steps`` Metropolis steps for a ``(rows, dim)`` batch of chains.

    The one definition of the sweep: the Pallas kernel runs it on one
    chain-block with SMEM scalars, the oracle (``ref.py``) on the whole
    batch with per-chain ``(rows, 1)`` columns, so the two agree by
    construction.  ``fns`` is :data:`STATIC_FNS` or :data:`RUNTIME_FNS`;
    ``lo``/``hi`` is the box, broadcastable to ``(rows, 1)``; ``live``
    (optional) masks every accept of a dead row, which then passes
    through bit-exactly.  ``reduce`` is the accumulator init's lane sum
    (``objective_math.init_acc``).

    Returns ``(x, fx)`` with ``fx`` shaped ``(rows, 1)``.
    """
    init_acc, combine, term, full_eval = fns
    rows, dim = x.shape
    coords = lax.broadcasted_iota(jnp.int32, (rows, dim), 1)

    if variant == "delta":
        S, logP, sgnP = init_acc(kid, x, reduce)
        fx = combine(kid, S, logP, sgnP, dim)

        def body(i, carry):
            x, fx, S, logP, sgnP = carry
            rbits, uval, uacc = _step_draws(seed, cidx, step0, i)
            d = (rbits % np.uint32(dim)).astype(jnp.int32)  # (rows, 1)
            onehot = coords == d
            xi_old = jnp.sum(jnp.where(onehot, x, 0.0), axis=1, keepdims=True)
            newval = lo + uval * (hi - lo)
            df = d.astype(x.dtype)
            s_old, p_old = term(kid, xi_old, df)
            s_new, p_new = term(kid, newval, df)
            S1 = tuple(a - o + n for a, o, n in zip(S, s_old, s_new))
            logP1 = (logP
                     - jnp.log(jnp.maximum(jnp.abs(p_old), 1e-30))
                     + jnp.log(jnp.maximum(jnp.abs(p_new), 1e-30)))
            sg = jnp.where(p_old < 0, -1.0, 1.0) * jnp.where(p_new < 0, -1.0, 1.0)
            sgnP1 = sgnP * sg.astype(sgnP.dtype)
            f1 = combine(kid, S1, logP1, sgnP1, dim)
            acc = uacc <= _accept_prob(fx, f1, T)  # (rows, 1)
            if live is not None:
                acc = acc & live
            x = jnp.where(onehot & acc, newval, x)
            fx = jnp.where(acc, f1, fx)
            S = tuple(jnp.where(acc, a1, a) for a1, a in zip(S1, S))
            logP = jnp.where(acc, logP1, logP)
            sgnP = jnp.where(acc, sgnP1, sgnP)
            return x, fx, S, logP, sgnP

        x, fx, *_ = lax.fori_loop(0, n_steps, body, (x, fx, S, logP, sgnP))
    else:  # full: paper-faithful O(dim) evaluation per step
        fx = full_eval(kid, x, dim)

        def body(i, carry):
            x, fx = carry
            rbits, uval, uacc = _step_draws(seed, cidx, step0, i)
            d = (rbits % np.uint32(dim)).astype(jnp.int32)
            onehot = coords == d
            newval = lo + uval * (hi - lo)
            x1 = jnp.where(onehot, newval, x)
            f1 = full_eval(kid, x1, dim)
            acc = uacc <= _accept_prob(fx, f1, T)
            if live is not None:
                acc = acc & live
            x = jnp.where(acc, x1, x)
            fx = jnp.where(acc, f1, fx)
            return x, fx

        x, fx = lax.fori_loop(0, n_steps, body, (x, fx))
    return x, fx


def _sweep_kernel(*refs, kid_static, n_steps: int, blk: int,
                  variant: str, with_live: bool = False,
                  with_chain_t: bool = False):
    # Ref layout: 5-or-6 SMEM control refs, then the VMEM tensor refs.
    # ``live`` (macro-tick serving path) is the per-slot level cursor —
    # blocks whose request has exhausted its planned ladder levels for
    # this macro-tick pass their state through bit-exactly (acc forced
    # to False; the counter-based RNG is stateless so no draws are
    # consumed on their behalf).  ``with_chain_t`` (replica-exchange
    # serving path) swaps the per-block SMEM temperature for a (blk, 1)
    # VMEM column so every chain — a parallel-tempering rung — anneals at
    # its own temperature inside one block.
    n_smem = 6 if with_live else 5
    kid_ref, seed_ref, step0_ref, t_ref, base_ref = refs[:5]
    live_ref = refs[5] if with_live else None
    vrefs = refs[n_smem:]
    if with_chain_t:
        x_ref, tc_ref, xo_ref, fo_ref = vrefs
    else:
        x_ref, xo_ref, fo_ref = vrefs
        tc_ref = None

    pid = pl.program_id(0)
    if kid_static is not None:
        # Concrete objective: compile the single branch (pre-runtime-dispatch
        # behavior — batch callers keep 1x objective math per proposal).
        kid = kid_static
        lo, hi = (np.float32(b) for b in om.BOX[kid])
        fns = STATIC_FNS
    else:
        kid = kid_ref[pid]      # runtime objective id: scalar per block
        lo, hi = om.box_rt(kid)
        fns = RUNTIME_FNS
    # Per-chain (blk, 1) temperature column, or the block's SMEM scalar —
    # broadcasting against the (blk, 1) accept shapes either way.
    T = t_ref[pid] if tc_ref is None else tc_ref[...]
    live = None if live_ref is None else live_ref[pid] != 0
    cidx = (base_ref[pid]
            + lax.broadcasted_iota(jnp.int32, (blk, 1), 0).astype(jnp.uint32))
    x, fx = sweep_chains(x_ref[...], T, seed_ref[pid], cidx, step0_ref[pid],
                         kid=kid, lo=lo, hi=hi, fns=fns, n_steps=n_steps,
                         variant=variant, live=live)
    xo_ref[...] = x
    fo_ref[...] = fx


def _per_block(v, n_blocks: int, dtype, name: str):
    """Broadcast a scalar — or validate a (n_blocks,) array — of SMEM input."""
    a = jnp.asarray(v, dtype).reshape(-1)
    if a.shape[0] == 1:
        return jnp.broadcast_to(a, (n_blocks,))
    if a.shape[0] != n_blocks:
        raise ValueError(
            f"{name} has {a.shape[0]} entries for a {n_blocks}-block grid; "
            f"pass a scalar or one entry per chain-block")
    return a


def _validate_kid(kid) -> None:
    """Reject out-of-range objective ids while they are still concrete.

    Runtime dispatch would otherwise fall through the ``jnp.where`` chains
    to kid 0 and silently anneal Schwefel.  Traced values can't be checked
    here — inside jit the serving engine's ids are already validated by
    SARequest, which is the only path that reaches this under a tracer.
    """
    if isinstance(kid, jax.core.Tracer):
        return
    arr = np.asarray(kid)
    if arr.size and bool(((arr < 0) | (arr >= om.N_KIDS)).any()):
        raise ValueError(
            f"objective id(s) {arr.tolist()} outside the kernel registry "
            f"[0, {om.N_KIDS})")


def metropolis_sweep_pallas(x, T, seed, step0, *, kid, n_steps: int,
                            blk: int = 256, variant: str = "delta",
                            interpret: bool = False, chain_base=None,
                            live=None, t_chain=None):
    """Run an N-step Metropolis sweep for all chains.

    Args:
      x: (chains, dim) float32 chain states.
      T: temperature — scalar, or (chains//blk,) array for per-block
         (per-serving-slot) temperatures.
      seed, step0: RNG stream coordinates; scalar or per-block arrays, so
         co-scheduled requests keep independent, placement-invariant streams.
      kid: registry objective id (objective_math.KID_*) — a **runtime**
         input: scalar, or (chains//blk,) int32 array for per-block
         (per-serving-slot) objectives.  Not baked into the compiled
         program; one lowering serves every registry objective.
      n_steps: Metropolis steps (paper's N).
      blk: chains per kernel block (multiple of 8).
      variant: 'delta' (O(1) updates) or 'full' (paper-faithful).
      chain_base: optional per-block global chain-index base (uint32,
         (chains//blk,)); defaults to ``block * blk`` (the single-job
         layout). The RNG stream of chain c in block b is indexed by
         ``chain_base[b] + c``, which is what makes a request's streams
         identical no matter which slots the scheduler packed it into.
      live: optional per-block level cursor (bool/int32, (chains//blk,)).
         A dead block (``live == 0``) passes its state through bit-exactly
         — every accept is masked off, so ``x`` is unchanged and no random
         stream advances (counter-based RNG draws are stateless).  The
         macro-tick engine uses this so co-batched requests with different
         remaining ladder depths fuse into one K-level dispatch.
      t_chain: optional per-chain temperatures (float32, (chains,) or
         (chains, 1)).  When given, each chain anneals at its own
         temperature (parallel-tempering rungs) and the per-block ``T`` is
         ignored; a block whose rows all carry the block temperature is
         bit-identical to the SMEM-scalar path (same broadcasting into the
         accept test).

    Returns (x_out, f_out): (chains, dim) and (chains,).
    """
    chains, dim = x.shape
    _validate_kid(kid)
    pad = (-chains) % blk
    if pad:
        if chain_base is not None or live is not None \
                or t_chain is not None or any(
                jnp.ndim(v) and jnp.size(v) > 1 for v in (T, seed, step0, kid)):
            raise ValueError(
                f"chains={chains} must be a multiple of blk={blk} when "
                "per-block control arrays are given")
        # Pad with dummy chains at the origin — inside every registry box
        # (a static om.BOX[kid] lookup is no longer possible: kid may be
        # traced).  Their streams use indices >= chains so real chains are
        # untouched. Sliced off below.
        x = jnp.concatenate(
            [x, jnp.zeros((pad, dim), x.dtype)], axis=0)
    n_chains_p = chains + pad
    grid = (n_chains_p // blk,)
    n_blocks = grid[0]

    # Concrete scalar kid -> compile the single objective branch; array or
    # traced kid -> runtime SMEM dispatch (one lowering for all objectives).
    kid_static = int(kid) if isinstance(kid, (int, np.integer)) else None
    with_live = live is not None
    with_chain_t = t_chain is not None
    kernel = functools.partial(
        _sweep_kernel, kid_static=kid_static, n_steps=n_steps, blk=blk,
        variant=variant, with_live=with_live, with_chain_t=with_chain_t)

    kid_arr = _per_block(kid, n_blocks, jnp.int32, "kid")
    seed_arr = _per_block(seed, n_blocks, jnp.uint32, "seed")
    step0_arr = _per_block(step0, n_blocks, jnp.uint32, "step0")
    t_arr = _per_block(T, n_blocks, jnp.float32, "T")
    if chain_base is None:
        base_arr = (jnp.arange(n_blocks, dtype=jnp.uint32)
                    * np.uint32(blk))
    else:
        base_arr = _per_block(chain_base, n_blocks, jnp.uint32, "chain_base")

    inputs = [kid_arr, seed_arr, step0_arr, t_arr, base_arr]
    n_smem = 5
    if with_live:
        inputs.append(_per_block(live, n_blocks, jnp.int32, "live"))
        n_smem = 6
    inputs.append(x)
    in_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)] * n_smem
                + [pl.BlockSpec((blk, dim), lambda i: (i, 0))])
    if with_chain_t:
        tc = jnp.asarray(t_chain, jnp.float32).reshape(-1, 1)
        if tc.shape[0] != chains:
            raise ValueError(
                f"t_chain has {tc.shape[0]} entries for {chains} chains")
        inputs.append(tc)
        in_specs.append(pl.BlockSpec((blk, 1), lambda i: (i, 0)))

    name = (f"metropolis_sweep_{variant}" if kid_static is None
            else f"metropolis_sweep_{variant}_k{kid_static}")
    x_out, f_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((blk, dim), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_chains_p, dim), x.dtype),
            jax.ShapeDtypeStruct((n_chains_p, 1), x.dtype),
        ],
        interpret=interpret,
        name=name + ("_lv" if with_live else "") +
             ("_ct" if with_chain_t else ""),
    )(*inputs)
    return x_out[:chains], f_out[:chains, 0]
