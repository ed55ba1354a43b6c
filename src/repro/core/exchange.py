"""Chain-exchange (crossover) operators for parallel SA.

The paper's V2 performs a deterministic *minimum crossover* at every
temperature level: all chains restart from the globally best state.  On the
GPU this is a Thrust reduce; on the TPU mesh it is a per-shard ``argmin``
followed by a tiny ``all_gather`` of per-shard champions — only
``devices × (dim + 1)`` floats move over the interconnect, exactly the
paper's "only function values are exchanged among workers".

Strategies
----------
``async``  : no exchange until the very end (paper V1).
``sync``   : minimum crossover each ``period`` levels (paper V2, period=1).
``sos``    : Synchronous with Occasional Solution exchanges (Onbasoglu &
             Özdamar [23]) — stochastic crossover: a chain adopts the
             champion only if better, or with Metropolis probability at the
             current temperature; keeps chain diversity.

Beyond the paper's family, the serving engine composes two *replica*
operators on the same segmented machinery (see docs/serving.md):

``pt_swap_segmented``    : parallel tempering — a deterministic even/odd
             Metropolis swap pass over a request's per-chain temperature
             ladder (Salazar & Toral's hybrid MC; the PT-RWM layout).
``pa_resample_segmented``: population annealing — Boltzmann-weighted
             multinomial resampling of a request's chain population at
             each temperature-level transition (Barash et al.).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import rng

#: Salts xor-ed into a request's RNG seed so the exchange-operator draws
#: (sos adoption / PT swap / PA resample) are independent of the sweep
#: kernel's (seed, chain, step) streams — all counter-based, so every
#: operator stays placement/preemption/width invariant.
SOS_SALT = np.uint32(0x5053D1B5)
PT_SALT = np.uint32(0x9E3779B9)
PA_SALT = np.uint32(0x7F4A7C15)

#: Per-chain workload-class codes threaded through the serving engine's
#: device program (one int8 per chain; pads and plain-sync/async chains
#: are PLAIN).
MCODE_PLAIN = 0
MCODE_SOS = 1
MCODE_PT = 2
MCODE_PA = 3

#: Fixed-point scale for PA resampling weights.  Integer cumulative sums
#: are exact and associative, so a tenant's inverse-CDF lookups are
#: bit-identical no matter which rows of a packed batch it occupies (a
#: float cumsum would leak other tenants' rounding into the comparison).
PA_WEIGHT_SCALE = 65536.0


def exchange_uniform(seed, salt, idx, step):
    """One counter-based uniform for an exchange operator: keyed on the
    request seed xor ``salt``, a logical index and the absolute ladder
    level — a stream family disjoint from the sweep kernel's draws."""
    _, u, _ = rng.draws3(jnp.asarray(seed, jnp.uint32) ^ salt, idx, step)
    return u


def local_champion(x, fx):
    """Best (x, f) among the local chains."""
    i = jnp.argmin(fx)
    return x[i], fx[i]


def global_champion(x, fx, axis_names=None):
    """Champion across local chains and (optionally) mesh axes.

    Inside ``shard_map`` with ``axis_names`` set, gathers one champion per
    shard and reduces replicatedly (identical result on all shards).
    """
    xb, fb = local_champion(x, fx)
    if axis_names:
        # Tiny collective: (devices, dim+1) floats.
        fall = lax.all_gather(fb, axis_names, tiled=False)  # (shards,)
        xall = lax.all_gather(xb, axis_names, tiled=False)  # (shards, dim)
        fall = fall.reshape(-1)
        xall = xall.reshape(-1, x.shape[-1])
        j = jnp.argmin(fall)
        xb, fb = xall[j], fall[j]
    return xb, fb


def exchange_sync(key, x, fx, T, axis_names=None):
    """Paper V2: every chain restarts from the global champion."""
    xb, fb = global_champion(x, fx, axis_names)
    x = jnp.broadcast_to(xb[None, :], x.shape)
    fx = jnp.full_like(fx, fb)
    return x, fx


def sos_adopt_prob(fx, fb, T):
    """SOS adoption probability for a chain at value ``fx`` offered the
    champion ``fb`` at temperature ``T`` (Onbasoglu–Özdamar semantics):

    - deficit ``d = fx - fb > T`` (champion strictly better by more than
      one temperature): adopt deterministically, ``p = 1``;
    - tie (``d = 0``): adopt with probability exactly ``1/2``;
    - within-T (``0 < d <= T``): interpolate, ``p = 1 - exp(-d/T)/2``
      (continuous in d, rising from 1/2 at a tie toward 1).

    The champion is a minimum over the population, so ``d >= 0`` always.
    """
    d = jnp.maximum(fx - fb, 0.0)
    t = jnp.maximum(T, 1e-30)
    p_within = 1.0 - 0.5 * jnp.exp(jnp.clip(-d / t, -80.0, 0.0))
    return jnp.where(d > t, jnp.ones_like(p_within), p_within)


def exchange_sos(key, x, fx, T, axis_names=None):
    """Stochastic crossover (SOS): adopt the champion deterministically when
    it is better by more than T, with probability 1/2 at a tie, and with an
    interpolated probability in between — keeps chain diversity by never
    forcing the whole population onto one state unless it dominates."""
    xb, fb = global_champion(x, fx, axis_names)
    u = jax.random.uniform(key, fx.shape, dtype=fx.dtype)
    adopt = u <= sos_adopt_prob(fx, fb, T)
    x = jnp.where(adopt[:, None], xb[None, :], x)
    fx = jnp.where(adopt, fb, fx)
    return x, fx


def exchange_none(key, x, fx, T, axis_names=None):
    return x, fx


# ------------------------------------------------------------------ segmented
# Multi-tenant serving (service/engine.py): chains from several independent
# requests are packed into one device batch, so the champion reduce must be
# *masked per request* — a tenant's chains may only ever see their own
# champion, never another job's.  ``seg`` assigns every chain its request id.

def segment_champion(x, fx, seg, num_segments: int):
    """Per-segment (per-request) champion: masked argmin over each tenant.

    Args:
      x: (chains, dim) states; fx: (chains,) values.
      seg: (chains,) int32 segment id per chain, in [0, num_segments).
      num_segments: static segment count (the slot-pool size bounds it).

    Returns (xb (num_segments, dim), fb (num_segments,), ib (num_segments,)):
    champion state/value/chain-index per segment.  Segments with no chains
    get ``fb = +inf`` and ``ib = chains`` (out of range — check before use).
    """
    n = fx.shape[0]
    fb = jnp.full((num_segments,), jnp.inf, fx.dtype).at[seg].min(fx)
    # First chain attaining its segment's min (deterministic tie-break).
    hit = fx == fb[seg]
    idx = jnp.where(hit, jnp.arange(n, dtype=jnp.int32), n)
    ib = jnp.full((num_segments,), n, jnp.int32).at[seg].min(idx)
    xb = x[jnp.minimum(ib, n - 1)]
    return xb, fb, ib


def exchange_sync_segmented(x, fx, seg, num_segments: int, adopt_mask=None):
    """Paper-V2 minimum crossover, tenant-isolated: every chain restarts
    from *its own request's* champion.  ``adopt_mask`` (chains,) lets the
    engine mix policies in one batch (False = async request / free slot:
    keep state untouched).

    Returns (x, fx, xb, fb): the exchanged chain state plus the per-segment
    champions, so callers can fold best-so-far without a second reduce."""
    xb, fb, ib = segment_champion(x, fx, seg, num_segments)
    valid = (ib < fx.shape[0])[seg]
    adopt = valid if adopt_mask is None else (valid & adopt_mask)
    x = jnp.where(adopt[:, None], xb[seg], x)
    fx = jnp.where(adopt, fb[seg], fx)
    return x, fx, xb, fb


def pt_swap_segmented(x, fx, t_rung, partner, pairlo, seed_c, lvl_abs, is_pt):
    """One deterministic even/odd parallel-tempering swap pass.

    Chains of a PT request each hold one rung of the request's temperature
    ladder; adjacent rungs propose a replica swap with the Metropolis
    acceptance ``min(1, exp((beta_l - beta_p)(f_l - f_p)))``.  The engine
    alternates even pairs (0,1)(2,3)… and odd pairs (1,2)(3,4)… by ladder
    level, precomputing *packed-row* partners host-side so the device pass
    is a pure gather.

    Args (all (chains,) unless noted):
      x: (chains, dim) states; fx: values.
      t_rung: per-chain rung temperature (any value for non-PT chains).
      partner: packed row index of this chain's swap partner for the
        current parity (self-row ⇒ no swap proposed).
      pairlo: logical ladder index of the *lower* rung of the pair (both
        partners carry the same value — keys one shared uniform so the
        accept decision is symmetric), uint32.
      seed_c: per-chain request seed (uint32).
      lvl_abs: absolute ladder level (uint32) — the RNG step counter.
      is_pt: bool mask; False rows pass through bitwise untouched.

    Returns (x, fx) with accepted pairs exchanged.  States swap, rung
    temperatures stay put (temperature-indexed replica layout) — so the
    sweep kernel's per-chain T never changes across swaps.
    """
    u = exchange_uniform(seed_c, PT_SALT, pairlo, lvl_abs)
    beta = 1.0 / jnp.maximum(t_rung, 1e-30)
    fp = fx[partner]
    log_a = (beta - beta[partner]) * (fx - fp)
    accept = u < jnp.exp(jnp.clip(log_a, -80.0, 0.0))
    swap = is_pt & (partner != jnp.arange(fx.shape[0], dtype=jnp.int32)) & accept
    # Gather from the pre-swap arrays only (fresh names, no aliasing).
    x_new = jnp.where(swap[:, None], x[partner], x)
    fx_new = jnp.where(swap, fp, fx)
    return x_new, fx_new


def pa_resample_segmented(x, fx, fb_seg, seg, seg_lo, seg_hi, dbeta_c,
                          seed_c, cidx, lvl_abs, is_pa):
    """Population-annealing resampling at a temperature-level transition.

    Each PA chain independently re-draws its ancestor from its own
    request's population with Boltzmann weight
    ``w_i ∝ exp(-dbeta (f_i - f_champion))`` where
    ``dbeta = 1/T_next - 1/T_cur`` (Barash et al.).  Weights are
    quantized to ``floor(w * PA_WEIGHT_SCALE)`` int32 before the cumsum:
    integer prefix sums are exact, so a tenant's inverse-CDF lookup is
    bit-identical regardless of which packed rows it occupies or what
    other tenants share the batch.  The champion row always carries the
    full-scale weight, so every segment's total is positive.

    Args:
      x: (chains, dim); fx: (chains,).
      fb_seg: (num_segments,) per-segment champion values (pre-resample).
      seg: (chains,) segment id; seg_lo/seg_hi: packed-row range
        [seg_lo, seg_hi) of each chain's own request (self-range
        [row, row+1) for non-PA rows).
      dbeta_c: (chains,) per-chain inverse-temperature increment (f32).
      seed_c / cidx / lvl_abs: RNG key material (uint32) — ``cidx`` is the
        *logical* chain index within the request, so the draw is invariant
        to where the request's rows land in the packed batch.
      is_pa: bool mask; False rows pass through bitwise untouched.

    Returns (x, fx) with each PA row replaced by its sampled ancestor.
    """
    # Quantized weights; masked rows weigh 0 so foreign tenants (and pads)
    # never enter a PA segment's CDF.  fb may be +inf on empty (pad)
    # segments, making the exponent NaN there — those rows are masked out.
    d = fx - fb_seg[seg]
    w = jnp.exp(jnp.clip(-dbeta_c * d, -80.0, 0.0))
    wq = jnp.where(is_pa, (w * PA_WEIGHT_SCALE).astype(jnp.int32), 0)
    cum = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(wq)])          # (n+1,) exclusive
    tot = cum[seg_hi] - cum[seg_lo]                            # per-chain pop mass
    u = exchange_uniform(seed_c, PA_SALT, cidx, lvl_abs)
    tgt = cum[seg_lo] + jnp.clip(
        jnp.floor(u * tot.astype(fx.dtype)).astype(jnp.int32), 0,
        jnp.maximum(tot - 1, 0))
    anc = jnp.clip(jnp.searchsorted(cum, tgt, side="right") - 1,
                   seg_lo, jnp.maximum(seg_hi - 1, seg_lo))
    take = is_pa & (tot > 0)
    x_new = jnp.where(take[:, None], x[anc], x)
    fx_new = jnp.where(take, fx[anc], fx)
    return x_new, fx_new


def serving_exchange(x, fx, seg, num_segments, adopt, mcode, t_rung, T_exch,
                     partner, pairlo, seg_lo, seg_hi, dbeta_c, seed_c,
                     cidx, lvl_abs, live):
    """The engine's composite per-level exchange over a mixed-class batch.

    One traced program covers every workload class; each stage is masked
    so an all-False mask is a bitwise identity for the other tenants:

      1. segmented champion reduce (always — feeds best-so-far folding);
      2. champion adoption: ``sync`` (deterministic) and ``sos``
         (stochastic, :func:`sos_adopt_prob`) chains;
      3. parallel-tempering even/odd swap pass (PT chains);
      4. population-annealing Boltzmann resample (PA chains).

    ``T_exch`` is the per-chain *schedule* temperature (block ladder value
    for plain/sos/pa chains); ``cidx`` the per-chain logical chain index
    (uint32); ``live`` masks out chains of finished or padded blocks
    inside a fused macro-tick.

    Returns (x, fx, xb, fb) like :func:`exchange_sync_segmented`.

    Each stage runs under a ``jax.named_scope`` (``champion``, ``adopt``,
    ``pt_swap``, ``pa_resample``), so a profiler trace times the stages
    apart; scopes are metadata and change no operation.
    """
    n = fx.shape[0]
    with jax.named_scope("champion"):
        xb, fb, ib = segment_champion(x, fx, seg, num_segments)
        valid = (ib < n)[seg] & live

    with jax.named_scope("adopt"):
        is_sos = mcode == MCODE_SOS
        u_sos = exchange_uniform(seed_c, SOS_SALT, cidx, lvl_abs)
        sos_take = is_sos & (u_sos <= sos_adopt_prob(fx, fb[seg], T_exch))
        take = valid & (adopt | sos_take)
        x = jnp.where(take[:, None], xb[seg], x)
        fx = jnp.where(take, fb[seg], fx)

    with jax.named_scope("pt_swap"):
        x, fx = pt_swap_segmented(x, fx, t_rung, partner, pairlo, seed_c,
                                  lvl_abs, (mcode == MCODE_PT) & live)
    with jax.named_scope("pa_resample"):
        x, fx = pa_resample_segmented(x, fx, fb, seg, seg_lo, seg_hi,
                                      dbeta_c, seed_c, cidx, lvl_abs,
                                      (mcode == MCODE_PA) & live)
    return x, fx, xb, fb


EXCHANGES = {
    "async": exchange_none,
    "sync": exchange_sync,
    "sos": exchange_sos,
}
