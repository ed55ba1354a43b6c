"""Continuous-batching SA serving engine.

The annealing analogue of a vLLM/LightLLM decode loop (launch/serve.py):

* a sharded pool of chain-block *slots* (slots.py, sharding.py) — the
  "decode batch", one shard per device on a 1-D ``(pool,)`` mesh;
* an admission scheduler (scheduler.py) packs queued requests into free
  slots — "prefill" — and places each request on a home shard;
* one engine **tick** advances every active slot by one temperature level
  (one N-step Metropolis sweep at that slot's own temperature, then a
  champion exchange masked per request);
* a request whose ladder / budget / accuracy target completes frees its
  slots *immediately* and the next queued request takes them — no tail
  latency from stragglers sharing the batch.

Invariants
----------
* **One tick = ``macro_k`` temperature levels** for every active slot
  (one when K=1, the classic tick).  ``tick_count`` always advances on
  the *ladder-level* clock — by K per active macro-tick — so a request's
  temperature ladder position is exactly its count of level-ticks in
  residence and every lifecycle timestamp keeps level units at any K.
  Admission, preemption, migration and fleet ops land only on macro-tick
  boundaries (the top of ``tick()``); within a macro-tick the K levels —
  including the per-level champion exchange — run fused in one device
  program with donated ping-pong state buffers (``_group_tick_fused``).
* **kid is runtime**: per-slot *objective id, temperature, RNG seed, step
  cursor and chain base* are runtime arrays threaded down to the kernel
  (one SMEM entry per block, indexed by ``program_id``) — none of them can
  cause recompilation.  Only *dimensionality and sweep length* remain
  compile-time constants, so active slots are grouped by ``(dim, N)``
  within each shard every tick and dispatched as one device program per
  ``(shard, dim, N)`` group: one compiled sweep program per device serves
  every registry objective, and growing ``SERVABLE`` never costs a
  recompile.  (Groups are additionally padded to power-of-two block
  counts to bound the number of compiled shapes.)
* **Tenant isolation**: champion reduces inside a packed group are
  segmented by request id — tenants never exchange states
  (core/exchange.py) — and placement-invariant RNG makes a request's
  trajectory bit-identical to its standalone single-tenant run.
* **Sharded pool** (sharding.py): ``EngineConfig.n_devices`` engine
  shards each own ``n_slots`` slots on their own mesh device.  The
  scheduler's placement layer homes each admitted request on the
  least-loaded compatible shard and rebalances via Russkov-style
  migration — checkpoint a :class:`~repro.service.slots.SwappedJob` on
  the overloaded shard, restore it on an underloaded one — and because
  restore is placement-invariant, a migrated trajectory is **bit-exact**
  versus an uninterrupted single-device run.  Requests never span shards.
* **Open-loop serving**: :meth:`SAServeEngine.run_stream` interleaves
  admission of an :class:`~repro.service.arrivals.ArrivalProcess` (e.g.
  seeded Poisson) with in-flight progress, stamping per-request lifecycle
  events (submit / admit / first-tick / preempted / resumed /
  complete-or-rejected, in both tick-time and wall-time) from which
  queueing-delay and time-to-first-tick percentiles are derived (see
  docs/serving.md).  All wall times — lifecycle stamps and the run's
  ``wall_s`` alike — come from one monotonic epoch
  (``time.perf_counter`` since engine construction), so a wall-clock
  adjustment mid-run can never skew a latency or throughput figure.
* **Preemption is bit-exact**: an active job checkpoints to a host-side
  :class:`~repro.service.slots.SwappedJob` (slot blocks + champion + RNG
  step cursor + temperature cursor) and resumes — possibly on different
  physical slots of a different shard — with a trajectory identical to an
  uninterrupted run, because the RNG is counter-based on logical (chain
  index, step) coordinates.  SLO admission control (scheduler.py) builds
  on it: the 'preempt' overload policy evicts the cheapest active jobs
  for an urgent arrival, 'reject' and 'degrade' bound queue growth at
  overload.
* **The fleet is elastic** (this PR): :meth:`SAServeEngine.drain` marks a
  shard draining — no new placements; its jobs are checkpoint-evacuated
  onto the survivors each tick (bounded by ``migration_budget``, highest
  effective priority first, shrinking or swapping to the queue when no
  survivor has full-width room) and the shard is retired once empty.
  :meth:`SAServeEngine.resize` composes drain/add for mid-stream fleet
  grow/shrink.  The scheduler's placement layer adds **watermark
  rebalancing** (background moves off shards above ``high_watermark``
  onto shards below ``low_watermark``, hysteresis by construction) and
  **proactive degrade** (shrink *running* degrade-class jobs —
  checkpoint, restore at fewer slots, never below ``min_chains`` — when
  the queue head fits nowhere).  Every moved or shrunk trajectory stays
  bit-exact versus an uninterrupted run with the same width schedule,
  because all three reuse the placement-invariant checkpoint/restore.
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
from collections import defaultdict
from functools import partial
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import exchange as exch
from repro.kernels import objective_math as om
from repro.kernels import ops
from repro.objectives import families as fam_mod
from repro.service.request import RequestResult, SARequest
from repro.service.scheduler import (AdmissionScheduler, QueueEntry,
                                     SchedulerConfig, ShardView)
from repro.service.sharding import EngineShard, make_shard, make_shards
from repro.service.slots import ActiveJob, SwappedJob
from repro.service.telemetry import NULL as NULL_TELEMETRY

# Every group program donates its input state buffer (the double buffer
# ping-pongs between launches).  Backends without donation support
# (CPU) warn instead of reusing the buffer — functionally identical, so
# silence exactly that warning.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable",
    category=UserWarning)

#: Known optima of the servable *continuous* (registry) objectives, keyed
#: by kernel id — derived from the family layer's name-keyed table so the
#: values live in exactly one place (objectives/families.py).  Schwefel is
#: the paper's normalized form, so its optimum is dim-free.  A continuous
#: request may only set ``target_error`` on an objective listed here —
#: :meth:`SAServeEngine.submit` validates it eagerly (a typed ValueError at
#: the frontend) instead of letting a KeyError wedge a slot mid-tick.
#: Permutation (QAP) requests never consult this dict: every registered
#: instance carries a verifiable ``best_known`` (``req.f_opt``).
F_OPT = {om.KID_BY_NAME[name]: v
         for name, v in fam_mod.F_OPT_BY_NAME.items()}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 8            # slots *per shard*
    chains_per_slot: int = 64   # chains per slot == kernel block size
    n_devices: int = 1          # engine shards on the 1-D (pool,) mesh;
                                # logical shards round-robin when fewer
                                # physical devices exist (sharding.py)
    variant: str = "delta"      # 'delta' (O(1) updates) | 'full' (paper)
    use_pallas: object = "auto"  # True | False | 'auto' (TPU only)
    interpret: bool = False     # Pallas interpret mode (tests on CPU)
    migration_budget: int = 1   # max cross-shard moves per tick (0 = no
                                # automatic rebalancing)
    macro_k: int = 1            # ladder levels fused into one device
                                # dispatch (a "macro-tick").  1 = the
                                # classic one-level tick; K>1 amortizes
                                # host packing/launch over K levels, and
                                # admission/preemption/migration land only
                                # on macro-tick boundaries.  Trajectories
                                # are bit-exact at any K (tests).
    scheduler: SchedulerConfig = dataclasses.field(
        default_factory=SchedulerConfig)

    def __post_init__(self):
        if self.n_devices < 1:
            raise ValueError(f"n_devices must be >= 1, got {self.n_devices}")
        if self.migration_budget < 0:
            raise ValueError("migration_budget must be >= 0")
        if self.macro_k < 1:
            raise ValueError(f"macro_k must be >= 1, got {self.macro_k}")


def _chain_controls(T_blk, seed_blk, base_blk, lvl0, mcode, t_rung, blk: int):
    """Expand per-block controls to the per-chain arrays the composite
    exchange consumes: the schedule temperature, the effective sweep
    temperature (PT chains anneal at their own rung, everyone else at the
    block's ladder value), the request seed, the logical chain index and
    the absolute ladder level."""
    n_blocks = jnp.asarray(T_blk).shape[0]
    sched = jnp.repeat(T_blk, blk)
    T_chain = jnp.where(mcode == exch.MCODE_PT, t_rung, sched)
    seed_c = jnp.repeat(seed_blk, blk)
    cidx = (jnp.repeat(base_blk, blk).astype(jnp.uint32)
            + jnp.tile(jnp.arange(blk, dtype=jnp.uint32), n_blocks))
    lvl_abs = jnp.repeat(lvl0.astype(jnp.uint32), blk)
    return sched, T_chain, seed_c, cidx, lvl_abs


@partial(jax.jit, static_argnames=("n_steps", "blk", "variant",
                                   "use_pallas", "interpret", "num_segments"),
         donate_argnums=(0,))
def _group_tick(x, kid_blk, T_blk, seed_blk, step0_blk, base_blk, lvl0_blk,
                dbeta_blk, seg, adopt, mcode, t_rung, partner, pairlo,
                seg_lo, seg_hi, *, n_steps: int, blk: int, variant: str,
                use_pallas: bool, interpret: bool, num_segments: int):
    """One temperature level for one dispatch group, on device.

    Sweep every block on its own objective (``kid_blk`` is a runtime
    input — mixed-objective groups share one lowering) at its own
    temperature — per *chain* when the block belongs to a parallel-
    tempering tenant (``t_rung``) — then the composite segmented exchange
    (core/exchange.serving_exchange): champion reduce, sync/sos adoption,
    PT even/odd swap, PA resample, each masked per workload class so a
    plain-SA-only batch is bitwise the classic path.  The champion is
    returned for every segment either way so the host can fold
    best-so-far.

    The three stages run under ``jax.named_scope``s — ``sa.controls``,
    ``sa.sweep``, ``sa.exchange`` — that name their device ops in a
    profiler trace (metadata only: the program is the same).  Every
    group program uses the same three.  ``x`` is **donated**, as in the
    fused program: the state stays on the device between levels while the
    group's membership is stable.
    """
    with jax.named_scope("sa.controls"):
        sched, T_chain, seed_c, cidx, lvl_abs = _chain_controls(
            T_blk, seed_blk, base_blk, lvl0_blk, mcode, t_rung, blk)
        dbeta_c = jnp.repeat(dbeta_blk, blk)
    with jax.named_scope("sa.sweep"):
        x, fx = ops.metropolis_sweep_slots(
            x, kid_blk, T_blk, seed_blk, step0_blk, base_blk,
            n_steps=n_steps, blk=blk, variant=variant, use_pallas=use_pallas,
            interpret=interpret, T_chain=T_chain)
    live = jnp.ones(fx.shape, bool)
    with jax.named_scope("sa.exchange"):
        return exch.serving_exchange(
            x, fx, seg, num_segments, adopt, mcode, t_rung, sched, partner,
            pairlo, seg_lo, seg_hi, dbeta_c, seed_c, cidx, lvl_abs, live)


@partial(jax.jit, static_argnames=("k", "n_steps", "blk", "variant",
                                   "use_pallas", "interpret",
                                   "num_segments"),
         donate_argnums=(0,))
def _group_tick_fused(x, kid_blk, T_lvls, seed_blk, step0_blk, base_blk,
                      levels_blk, lvl0_blk, dbeta_lvls, seg, adopt, mcode,
                      t_rung, partner2, pairlo2, seg_lo, seg_hi, *, k: int,
                      n_steps: int, blk: int, variant: str, use_pallas: bool,
                      interpret: bool, num_segments: int):
    """K temperature levels for one dispatch group, in one device program.

    The macro-tick: an on-device ``fori_loop`` over ``k`` iterations of
    [one-level sweep + composite segmented exchange] — exactly the K=1
    ``_group_tick`` body K times, so each level's floating-point stream is
    identical to K separate dispatches.  Per-level controls:

    * ``T_lvls`` is ``(k, n_blocks)`` — each block's host-precomputed
      temperature ladder slice, one SMEM row per level — and
      ``dbeta_lvls`` its PA inverse-temperature increments (0 elsewhere);
    * level ``i`` sweeps with RNG step cursor ``step0 + i*n_steps`` at
      absolute ladder level ``lvl0_blk + i`` (the exchange RNG counter);
    * ``levels_blk`` is the per-slot level cursor: blocks whose request
      has fewer than ``k`` planned levels go *dead* (``live = i <
      levels_blk``) — the kernel masks their accepts so state passes
      through bit-exactly, and the per-class masks keep their chains out
      of every exchange stage;
    * ``partner2`` / ``pairlo2`` are ``(2, chains)``: row ``i % 2`` holds
      each PT chain's swap partner for that level's even/odd parity
      (host-precomputed from its own job's absolute level).

    Per-level champions come back stacked — ``(k, num_segments)`` values
    and ``(k, num_segments, dim)`` states — for the host to fold level by
    level (truncating at early finishes), plus ``fx_keep``: each chain's
    post-exchange objective value at its *last live* level (dead
    iterations re-derive f(x) bitwise differently, so the live value is
    carried, not recomputed) — the population-annealing ESS controller
    reads it at the boundary.  ``x`` is **donated**: the engine's double
    buffer ping-pongs between launches, so chain state never round-trips
    to host while a group's membership is stable.
    """
    dim = x.shape[1]

    def body(i, carry):
        x, fx_keep, fb_all, xb_all = carry
        live = i < levels_blk                       # (n_blocks,) cursor
        with jax.named_scope("sa.controls"):
            T_i = lax.dynamic_index_in_dim(T_lvls, i, 0, keepdims=False)
            db_i = lax.dynamic_index_in_dim(dbeta_lvls, i, 0,
                                            keepdims=False)
            step0_i = step0_blk + jnp.uint32(n_steps) * i.astype(jnp.uint32)
            sched, T_chain, seed_c, cidx, lvl_abs = _chain_controls(
                T_i, seed_blk, base_blk, lvl0_blk + i.astype(jnp.uint32),
                mcode, t_rung, blk)
            live_c = jnp.repeat(live, blk)
            prt = lax.dynamic_index_in_dim(partner2, i % 2, 0,
                                           keepdims=False)
            plo = lax.dynamic_index_in_dim(pairlo2, i % 2, 0,
                                           keepdims=False)
            dbeta_c = jnp.repeat(db_i, blk)
        with jax.named_scope("sa.sweep"):
            x, fx = ops.metropolis_sweep_slots(
                x, kid_blk, T_i, seed_blk, step0_i, base_blk,
                n_steps=n_steps, blk=blk, variant=variant,
                use_pallas=use_pallas, interpret=interpret, live=live,
                T_chain=T_chain)
        with jax.named_scope("sa.exchange"):
            x, fx, xb, fb = exch.serving_exchange(
                x, fx, seg, num_segments, adopt, mcode, t_rung, sched, prt,
                plo, seg_lo, seg_hi, dbeta_c, seed_c, cidx, lvl_abs, live_c)
        fx_keep = jnp.where(live_c, fx, fx_keep)
        return x, fx_keep, fb_all.at[i].set(fb), xb_all.at[i].set(xb)

    fb0 = jnp.full((k, num_segments), jnp.inf, x.dtype)
    xb0 = jnp.zeros((k, num_segments, dim), x.dtype)
    fx0 = jnp.zeros((x.shape[0],), x.dtype)
    return lax.fori_loop(0, k, body, (x, fx0, fb0, xb0))


@partial(jax.jit, static_argnames=("n_steps", "blk", "use_pallas",
                                   "interpret", "num_segments"),
         donate_argnums=(0,))
def _group_tick_qap(x, F_blk, D_blk, T_blk, seed_blk, step0_blk, base_blk,
                    lvl0_blk, seg, adopt, mcode, t_rung, partner, pairlo,
                    seg_lo, seg_hi, *, n_steps: int, blk: int,
                    use_pallas: bool, interpret: bool, num_segments: int):
    """One temperature level for one *permutation-family* dispatch group.

    The QAP counterpart of :func:`_group_tick`: the same control layout
    and the same composite segmented exchange (dtype-agnostic over the
    chain states, so int32 permutations ride it unchanged), but the sweep
    is the pairwise-exchange QAP kernel and the per-block runtime operands
    are the flow/distance matrices (packed ``(n_blocks*n, n)``) instead of
    an objective id.  Chain states ``x`` are int32; objective values stay
    float32 (exact for the integer-valued instances).  No ``variant``/
    ``dbeta``: the QAP sweep is always delta-evaluated (bitwise equal to a
    full evaluation) and permutation requests are method-'sa' only, so the
    PA reweighting increment is identically zero.  A separate jit (typed
    on int32 x) naturally pins one compiled program per family.  ``x``
    is donated, as in :func:`_group_tick`.
    """
    with jax.named_scope("sa.controls"):
        sched, T_chain, seed_c, cidx, lvl_abs = _chain_controls(
            T_blk, seed_blk, base_blk, lvl0_blk, mcode, t_rung, blk)
    with jax.named_scope("sa.sweep"):
        x, fx = ops.qap_sweep_slots(
            x, F_blk, D_blk, T_blk, seed_blk, step0_blk, base_blk,
            n_steps=n_steps, blk=blk, use_pallas=use_pallas,
            interpret=interpret)
    live = jnp.ones(fx.shape, bool)
    with jax.named_scope("sa.exchange"):
        return exch.serving_exchange(
            x, fx, seg, num_segments, adopt, mcode, t_rung, sched, partner,
            pairlo, seg_lo, seg_hi, jnp.zeros_like(fx), seed_c, cidx,
            lvl_abs, live)


@partial(jax.jit, static_argnames=("k", "n_steps", "blk", "use_pallas",
                                   "interpret", "num_segments"),
         donate_argnums=(0,))
def _group_tick_qap_fused(x, F_blk, D_blk, T_lvls, seed_blk, step0_blk,
                          base_blk, levels_blk, lvl0_blk, seg, adopt, mcode,
                          t_rung, partner2, pairlo2, seg_lo, seg_hi, *,
                          k: int, n_steps: int, blk: int, use_pallas: bool,
                          interpret: bool, num_segments: int):
    """K temperature levels for one permutation-family group, fused.

    Mirrors :func:`_group_tick_fused` level by level — same live-cursor
    masking, per-level champion stacks and donated ping-pong state buffer
    — with the QAP sweep in place of the Metropolis one.  The champion
    carry is typed explicitly (float32 values, int32 states): the
    continuous path types both off ``x.dtype``, which is exactly what an
    int32 state buffer must not do.  ``fx_keep`` is carried for interface
    parity (the PA controller never reads it here — permutation requests
    are method-'sa' only).
    """
    dim = x.shape[1]

    def body(i, carry):
        x, fx_keep, fb_all, xb_all = carry
        live = i < levels_blk                       # (n_blocks,) cursor
        with jax.named_scope("sa.controls"):
            T_i = lax.dynamic_index_in_dim(T_lvls, i, 0, keepdims=False)
            step0_i = step0_blk + jnp.uint32(n_steps) * i.astype(jnp.uint32)
            sched, T_chain, seed_c, cidx, lvl_abs = _chain_controls(
                T_i, seed_blk, base_blk, lvl0_blk + i.astype(jnp.uint32),
                mcode, t_rung, blk)
            live_c = jnp.repeat(live, blk)
            prt = lax.dynamic_index_in_dim(partner2, i % 2, 0,
                                           keepdims=False)
            plo = lax.dynamic_index_in_dim(pairlo2, i % 2, 0,
                                           keepdims=False)
        with jax.named_scope("sa.sweep"):
            x, fx = ops.qap_sweep_slots(
                x, F_blk, D_blk, T_i, seed_blk, step0_i, base_blk,
                n_steps=n_steps, blk=blk, use_pallas=use_pallas,
                interpret=interpret, live=live)
        with jax.named_scope("sa.exchange"):
            x, fx, xb, fb = exch.serving_exchange(
                x, fx, seg, num_segments, adopt, mcode, t_rung, sched, prt,
                plo, seg_lo, seg_hi, jnp.zeros_like(fx), seed_c, cidx,
                lvl_abs, live_c)
        fx_keep = jnp.where(live_c, fx, fx_keep)
        return x, fx_keep, fb_all.at[i].set(fb), xb_all.at[i].set(xb)

    fb0 = jnp.full((k, num_segments), jnp.inf, jnp.float32)
    xb0 = jnp.zeros((k, num_segments, dim), x.dtype)
    fx0 = jnp.zeros((x.shape[0],), jnp.float32)
    return lax.fori_loop(0, k, body, (x, fx0, fb0, xb0))


def _pt_partners(n: int, parity: int):
    """Logical even/odd swap partners for an ``n``-rung PT ladder.

    Parity 0 pairs rungs (0,1)(2,3)…, parity 1 pairs (1,2)(3,4)…; a rung
    without a partner at this parity (rung 0 on odd passes, the last rung
    when the count doesn't divide) is its own partner — the device pass
    treats self-partners as "no swap proposed".  Returns
    ``(partner int32, pairlo uint32)`` with ``pairlo`` the lower logical
    rung of each pair — the shared RNG key that makes both partners draw
    the same accept uniform.
    """
    lg = np.arange(n, dtype=np.int64)
    if parity == 0:
        p = lg ^ 1
    else:
        p = np.where(lg == 0, lg, ((lg - 1) ^ 1) + 1)
    p = np.where(p < n, p, lg)
    return p.astype(np.int32), np.minimum(lg, p).astype(np.uint32)


def _job_mcode(req: SARequest) -> int:
    """Per-chain workload-class code (core/exchange) for a request."""
    if req.method == "pt":
        return exch.MCODE_PT
    if req.method == "pa":
        return exch.MCODE_PA
    return exch.MCODE_SOS if req.exchange == "sos" else exch.MCODE_PLAIN


def _pa_dbeta(t: float, rho: float) -> float:
    """PA inverse-temperature increment across one cooling step, in
    float64 host math (cast to f32 at the SMEM boundary): the Boltzmann
    reweighting exponent between level temperature ``t`` and the next."""
    return 1.0 / (t * rho) - 1.0 / t


class SAServeEngine:
    """Multi-tenant annealing server: one device program per (shard, group)."""

    def __init__(self, cfg: Optional[EngineConfig] = None, telemetry=None):
        # Build a fresh default per engine: a mutable-default-argument
        # EngineConfig() would be evaluated once and shared by every engine
        # constructed without a config (tests pin this down).
        cfg = EngineConfig() if cfg is None else cfg
        self.cfg = cfg
        self.shards: List[EngineShard] = make_shards(
            cfg.n_devices, cfg.n_slots, cfg.chains_per_slot)
        self.scheduler = AdmissionScheduler(cfg.scheduler)
        # Observability is opt-in and purely host-side: the default NULL
        # telemetry no-ops every hook (no span objects, no metrics, no
        # behavior change), and an enabled Telemetry never touches a
        # device buffer or an admission decision — trajectories stay
        # bit-exact with tracing on (tests + serve_sa --check --trace).
        self.telemetry = NULL_TELEMETRY if telemetry is None else telemetry
        self.scheduler.telemetry = self.telemetry
        self.results: List[RequestResult] = []
        self.tick_count = 0
        self.n_submitted = 0          # requests offered via submit(): the
                                      # denominator for terminal accounting
        self.sweeps_done = 0          # block-sweeps (slot x level): also the
                                      # occupancy numerator (active slot-ticks)
        self.group_launches = 0
        self.preemptions = 0          # swap-outs performed
        self.rejections = 0           # SLO admission-control drops
        self.migrations = 0           # cross-shard rebalancing moves
        self.shrinks = 0              # proactive-degrade width reductions
        self.truncations = 0          # finish-deadline ladder truncations
        self.slot_ticks = 0           # Σ over ticks of fleet slot count —
                                      # the occupancy denominator (the
                                      # fleet is elastic, so ticks x slots
                                      # is no longer a constant product)
        self.retired_shards: List[Tuple[int, int]] = []  # (index, tick)
        self._next_shard_index = cfg.n_devices   # shard ids are stable and
                                                 # never reused (resize/add)
        self._ops: List[Tuple[int, int, object]] = []  # (tick, seq, fn)
        self._op_seq = 0
        # Closed-loop controller (service/autoscaler.py): when attached,
        # it samples fleet signals at the top of each tick and may call
        # resize()/schedule_op() itself.  None = no control plane.
        self.controller = None
        self._use_pallas = ops.resolve_use_pallas(cfg.use_pallas)
        if self._use_pallas and cfg.chains_per_slot % 8:
            raise ValueError(
                f"chains_per_slot={cfg.chains_per_slot} must be a multiple "
                "of 8 (TPU sublanes) on the Pallas path")
        self._epoch = time.perf_counter()
        # Phase spans and the sub-spans under them share the engine's
        # monotonic epoch; the NULL telemetry hands back one shared no-op
        # timer for both (zero allocation).
        self._pt = self.telemetry.make_phase_timer(self._now)
        self._sub = self.telemetry.make_subphase_timer(self._now)
        if self.telemetry.trace is not None:
            self.telemetry.trace.bind_clock(self._now)
        #: req_id -> (arrival_time in ticks, submit wall time): lifecycle
        #: info that must survive the queue (the scheduler only keeps the
        #: submit tick).
        self._submit_info: Dict[int, Tuple[float, float]] = {}

    @property
    def use_pallas(self) -> bool:
        """Whether sweeps run the Pallas kernels (``cfg.use_pallas``
        resolved; ``'auto'`` means on a TPU backend only)."""
        return self._use_pallas

    def _now(self) -> float:
        """Wall seconds since engine construction (the engine epoch).

        Monotonic (``time.perf_counter``): every wall-clock stamp the
        engine emits — lifecycle events *and* ``run_stream``'s ``wall_s``
        — shares this epoch, so intervals between them are meaningful and
        immune to wall-clock adjustments.
        """
        return time.perf_counter() - self._epoch

    # ------------------------------------------------------------ frontend
    def submit(self, req: SARequest, arrival_time: Optional[float] = None
               ) -> None:
        """Enqueue ``req``.  ``arrival_time`` (in ticks, may be fractional)
        is the offered-load timestamp for open-loop runs; it defaults to
        the submit tick (closed-loop batch submission)."""
        need = req.slots_needed(self.cfg.chains_per_slot)
        if need > self.cfg.n_slots:
            raise ValueError(
                f"request {req.req_id} needs {need} slots > the per-shard "
                f"pool of {self.cfg.n_slots}; requests never span shards — "
                "lower n_chains or grow n_slots")
        if (req.target_error is not None
                and req.family == fam_mod.FAMILY_CONTINUOUS
                and req.kid not in F_OPT):
            # Validate here, not mid-tick: an unguarded F_OPT lookup in the
            # finish check would raise KeyError after admission and wedge
            # the request's slots for good.  Permutation requests skip the
            # check: every registered QAP instance carries a best_known.
            raise ValueError(
                f"request {req.req_id} sets target_error but objective "
                f"{req.objective!r} has no registered optimum in "
                "engine.F_OPT; register one or drop target_error")
        if (req.req_id in self._submit_info
                or any(job.req.req_id == req.req_id
                       for _, job in self._iter_jobs())
                or any(r.req_id == req.req_id
                       for r in self.scheduler.pending)):
            raise ValueError(
                f"request id {req.req_id} is already queued, swapped out or "
                "in flight; req_ids must be unique among live requests")
        self._submit_info[req.req_id] = (
            float(self.tick_count if arrival_time is None else arrival_time),
            self._now())
        self.scheduler.submit(req, self.tick_count)
        self.n_submitted += 1
        if self.telemetry.trace is not None:
            self.telemetry.trace.request_begin(
                req.req_id, objective=req.objective, dim=req.dim,
                n_chains=req.n_chains, tick=self.tick_count)

    # ----------------------------------------------------------- shard views
    def _iter_jobs(self) -> Iterator[Tuple[EngineShard, ActiveJob]]:
        for shard in self.shards:
            for job in shard.rids.jobs.values():
                yield shard, job

    def _view(self, shard: EngineShard) -> ShardView:
        jobs = tuple(shard.rids.jobs.values())
        return ShardView(
            index=shard.index, free_slots=shard.pool.n_free, active=jobs,
            shapes=frozenset((j.req.family, j.req.dim, j.req.N)
                             for j in jobs))

    def _shard(self, index: int) -> EngineShard:
        """Shard by stable index.  Indices are identities, not positions:
        a retired shard leaves a gap and added shards get fresh ids."""
        for shard in self.shards:
            if shard.index == index:
                return shard
        raise ValueError(f"no live shard with index {index}")

    @property
    def live_shards(self) -> List[EngineShard]:
        """Shards accepting new placements (not draining)."""
        return [s for s in self.shards if not s.draining]

    @property
    def pool(self):
        """Single-shard convenience alias (tests, notebooks).  Multi-shard
        engines have no 'the pool' — address ``engine.shards[i].pool``."""
        if len(self.shards) == 1:
            return self.shards[0].pool
        raise AttributeError(
            f"engine has {len(self.shards)} shards: use shards[i].pool")

    @property
    def rids(self):
        """Single-shard convenience alias, like :attr:`pool`."""
        if len(self.shards) == 1:
            return self.shards[0].rids
        raise AttributeError(
            f"engine has {len(self.shards)} shards: use shards[i].rids")

    @property
    def n_active(self) -> int:
        return sum(len(s.rids.jobs) for s in self.shards)

    @property
    def done(self) -> bool:
        return self.n_active == 0 and len(self.scheduler) == 0

    # ----------------------------------------------------------- admission
    def _admit(self) -> None:
        cps = self.cfg.chains_per_slot
        budget = self.cfg.migration_budget
        pt = self._pt          # phase spans: planning = 'schedule',
        #                        executing the plans = 'admit'
        # Drain evacuation has first claim on the per-tick move budget:
        # jobs leave draining shards (migrate whole / shrink-migrate /
        # swap to queue, in that order of preference) so the shards can
        # retire.  Draining shards take no new placements — every view
        # handed to the planners below is a survivor.
        if any(s.draining for s in self.shards):
            budget -= self._evacuate_draining(budget)
            self._retire_drained()
        with pt("schedule"):
            views = {s.index: self._view(s) for s in self.live_shards}
            # Head defrag: if the queue head fits on no single shard but
            # the pool as a whole has room, migrate jobs off a donor shard
            # (checkpoint/restore, bit-exact) so the head becomes
            # admissible this very tick.  Snapshots are rebuilt only for
            # the (budget-bounded, usually zero) shards a move touched.
            moves = self.scheduler.plan_migrations(
                list(views.values()), cps, self.tick_count, budget)
        with pt("admit"):
            for rid, src, dst in moves:
                self._migrate_job(self._shard(src), rid, self._shard(dst))
        budget -= len(moves)
        for si in {si for move in moves for si in move[1:]}:
            views[si] = self._view(self._shard(si))
        # Proactive degrade: when migration cannot seat the head (the
        # pool is genuinely full), shrink running degrade-class jobs of
        # strictly lower effective priority — checkpoint/restore at
        # fewer slots, never below their floor — until it fits.
        shrinks = []
        if not moves and self.cfg.scheduler.proactive_degrade:
            with pt("schedule"):
                shrinks = self.scheduler.plan_shrinks(
                    list(views.values()), cps, self.tick_count,
                    self.cfg.scheduler.shrink_budget)
            with pt("admit"):
                for rid, si, keep_slots in shrinks:
                    self._shrink_job(self._shard(si), rid, keep_slots)
                    views[si] = self._view(self._shard(si))
        # Watermark rebalancing: background load-driven moves with
        # whatever move budget the head didn't need.  Skipped on ticks
        # head-defrag or a proactive shrink fired — the slots they freed
        # are earmarked for the head and must survive untouched until
        # admission below seats it (a rebalance move could otherwise
        # land new work on the shrink's shard, wasting the irreversible
        # width cut).
        if not moves and not shrinks:
            with pt("schedule"):
                rmoves = self.scheduler.plan_rebalance(
                    list(views.values()), self.tick_count, budget)
            with pt("admit"):
                for rid, src, dst in rmoves:
                    self._migrate_job(self._shard(src), rid,
                                      self._shard(dst))
            for si in {si for move in rmoves for si in move[1:]}:
                views[si] = self._view(self._shard(si))
        # Then one queue walk across all shards (scheduler.admit_sharded):
        # every entry, in effective-priority order, is tried at full
        # width on every shard — least-loaded first, (dim, N)-locality
        # tie-break — before its degrade/preempt fallback may fire, and
        # the preemption budget bounds evictions per tick across shards.
        with pt("schedule"):
            plan = self.scheduler.admit_sharded(
                list(views.values()), cps, self.tick_count)
        # Execution order matters: rejections first (they free nothing
        # but must be stamped this tick), then evictions (freeing slots
        # the plan's admissions count on), then placements.
        with pt("admit"):
            for entry in plan.rejected:
                self._reject(entry)
            for rid, si in plan.evict:
                self._swap_out(self._shard(si), rid)
            for entry, granted_slots, si in plan.admitted:
                with self._sub("admit.place"):
                    self._place(self._shard(si), entry, granted_slots)

    def _place(self, shard: EngineShard, entry: QueueEntry,
               granted_slots: int) -> None:
        tel = self.telemetry
        if tel.enabled:
            tel.m_placements.inc(1, str(shard.index))
        if entry.swapped is not None:       # swap-in: bit-exact resume
            job = entry.swapped.job
            job.resumed_ticks.append(self.tick_count)
            shard.rids.alloc(job)
            with self._sub("admit.restore"):
                job.slots = shard.pool.restore(job.rid,
                                               entry.swapped.blocks)
            job.home_shard = shard.index
            if tel.enabled:
                tel.decision(self.tick_count, "resume",
                             req_id=job.req.req_id, shard=shard.index,
                             slots=len(job.slots))
                if tel.trace is not None:
                    tel.trace.request_instant(
                        job.req.req_id, "resume", shard=shard.index,
                        tick=self.tick_count)
            return
        req = entry.req
        arrival, submit_wall = self._submit_info.pop(
            req.req_id, (float(entry.submit_tick), float("nan")))
        job = ActiveJob(req=req, rid=-1, slots=[], T=req.T0,
                        submit_tick=entry.submit_tick,
                        start_tick=self.tick_count,
                        arrival_time=arrival,
                        submit_wall=submit_wall,
                        admit_wall=self._now(),
                        home_shard=shard.index,
                        levels_limit=req.n_levels)
        shard.rids.alloc(job)
        with self._sub("admit.init_state"):
            job.slots = shard.pool.assign(job.rid, req,
                                          n_slots=granted_slots)
        job.granted_chains = granted_slots * self.cfg.chains_per_slot
        if tel.enabled:
            tel.decision(self.tick_count, "admit", req_id=req.req_id,
                         shard=shard.index, granted_slots=granted_slots,
                         requested_chains=req.n_chains,
                         granted_chains=job.granted_chains)
            if tel.trace is not None:
                tel.trace.request_instant(
                    req.req_id, "admit", shard=shard.index,
                    granted_chains=job.granted_chains,
                    tick=self.tick_count)

    def _swap_out(self, shard: EngineShard, rid: int) -> None:
        """Preempt: checkpoint a job's device-visible state to host, free
        its slots, and re-queue it for a bit-exact resume (on whichever
        shard next has room — swap-in doubles as migration)."""
        job = shard.rids.jobs[rid]
        with self._sub("admit.restore"):
            blocks = shard.pool.checkpoint(rid)
        shard.pool.release(rid)
        shard.rids.free(rid)
        job.slots = []
        job.rid = -1
        job.preempted_ticks.append(self.tick_count)
        self.scheduler.requeue(SwappedJob(job=job, blocks=blocks))
        self.preemptions += 1
        tel = self.telemetry
        if tel.enabled:
            tel.decision(self.tick_count, "preempt",
                         req_id=job.req.req_id, shard=shard.index,
                         level=job.level)
            if tel.trace is not None:
                tel.trace.request_instant(
                    job.req.req_id, "preempt", shard=shard.index,
                    level=job.level, tick=self.tick_count)

    def _migrate_job(self, src: EngineShard, rid: int,
                     dst: EngineShard) -> None:
        """Move a resident job between shards without a queue round-trip:
        checkpoint on ``src``, restore on ``dst`` in the same tick.  The
        job keeps annealing this tick (on its new device); the trajectory
        is bit-exact because restore is placement-invariant."""
        job = src.rids.jobs[rid]
        with self._sub("admit.restore"):
            blocks = src.pool.checkpoint(rid)
            src.pool.release(rid)
            src.rids.free(rid)
            dst.rids.alloc(job)
            job.slots = dst.pool.restore(job.rid, blocks)
        job.home_shard = dst.index
        job.migrated_ticks.append(self.tick_count)
        self.migrations += 1
        tel = self.telemetry
        if tel.enabled:
            tel.decision(self.tick_count, "migrate",
                         req_id=job.req.req_id, src=src.index,
                         dst=dst.index, level=job.level)
            if tel.trace is not None:
                tel.trace.request_instant(
                    job.req.req_id, "migrate", src=src.index,
                    dst=dst.index, tick=self.tick_count)

    def migrate(self, req_id: int, to_shard: int) -> bool:
        """Move the in-flight request ``req_id`` to shard ``to_shard``.

        The operator/test entry point for forcing a cross-shard move at a
        chosen temperature level (the scheduler's rebalancer calls the
        same checkpoint/restore path).  Returns False if the request is
        not active, already home, the target shard lacks room, or the
        target is draining (it takes no new placements).
        """
        dst = self._shard(to_shard)     # ValueError on unknown/retired ids
        if dst.draining:
            return False
        for shard, job in self._iter_jobs():
            if job.req.req_id == req_id:
                if shard.index == to_shard \
                        or dst.pool.n_free < len(job.slots):
                    return False
                self._migrate_job(shard, job.rid, dst)
                return True
        return False

    def preempt(self, req_id: int) -> bool:
        """Swap out the in-flight request ``req_id`` (False if not active).

        The scheduler's 'preempt' overload policy calls the same swap-out
        path; this is the operator/test entry point for preempting at a
        chosen temperature level.
        """
        for shard, job in list(self._iter_jobs()):
            if job.req.req_id == req_id:
                self._swap_out(shard, job.rid)
                return True
        return False

    # -------------------------------------------------------- elastic fleet
    def _record_shrink(self, job: ActiveJob, from_chains: int,
                       self_driven: bool = False) -> None:
        job.granted_chains = len(job.slots) * self.cfg.chains_per_slot
        job.shrunk_ticks.append(self.tick_count)
        event = (job.level, from_chains, job.granted_chains)
        # Self-driven (PA ESS) shrinks are re-derived by a standalone
        # replay from the identical fx stream; recording them apart keeps
        # the --check oracle from re-applying them as an external schedule.
        if self_driven:
            job.pa_shrink_events.append(event)
        else:
            job.shrink_events.append(event)
        self.shrinks += 1
        tel = self.telemetry
        if tel.enabled:
            kind = "pa_shrink" if self_driven else "shrink"
            tel.decision(self.tick_count, kind,
                         req_id=job.req.req_id, shard=job.home_shard,
                         level=job.level, from_chains=from_chains,
                         to_chains=job.granted_chains)
            if tel.trace is not None:
                tel.trace.request_instant(
                    job.req.req_id, kind, from_chains=from_chains,
                    to_chains=job.granted_chains, tick=self.tick_count)

    def _maybe_pa_shrink(self, shard: EngineShard, job: ActiveJob,
                         fx_job: np.ndarray) -> None:
        """Population-annealing self-driven width controller.

        At a macro-tick boundary, estimate the effective sample size of
        the job's population under the *next* level transition's
        Boltzmann reweighting — ``job.T`` has already advanced, so the
        increment is ``1/(T·rho) − 1/T`` — and halve the slot footprint
        when ``ESS/width`` falls below the request's ``pa_ess_ratio``: a
        concentrated population doesn't need its lanes, and the freed
        slots go back to admission.  Purely a function of the job's own
        (bit-exact) fx stream and float64 host math, so a standalone
        replay re-derives every one of these shrinks at the same levels.
        """
        req = job.req
        if req.method != "pa" or len(job.slots) <= 1:
            return
        db = _pa_dbeta(job.T, req.rho)
        w = np.exp(-db * (fx_job.astype(np.float64) - float(fx_job.min())))
        ess = float(w.sum()) ** 2 / float((w * w).sum())
        if ess / fx_job.shape[0] < req.pa_ess_ratio:
            self._shrink_job(shard, job.rid, max(1, len(job.slots) // 2),
                             self_driven=True)

    def _shrink_job(self, shard: EngineShard, rid: int,
                    keep_slots: int, self_driven: bool = False) -> None:
        """Proactive degrade in place: checkpoint, drop the tail blocks,
        restore ``keep_slots`` blocks on the same shard.  Surviving
        chains keep logical indices [0, keep_slots * cps) — their
        trajectories (and the job's best-so-far champion) are untouched;
        only the width schedule changes, which a standalone replay of the
        same schedule reproduces bit-exactly (``run_standalone``)."""
        job = shard.rids.jobs[rid]
        if not 0 < keep_slots < len(job.slots):
            raise ValueError(
                f"keep_slots must be in [1, {len(job.slots) - 1}], "
                f"got {keep_slots}")
        from_chains = job.granted_chains
        with self._sub("admit.restore"):
            blocks = shard.pool.checkpoint(rid)[:keep_slots]
            shard.pool.release(rid)
            job.slots = shard.pool.restore(rid, blocks)
        self._record_shrink(job, from_chains, self_driven=self_driven)

    def _shrink_migrate(self, src: EngineShard, rid: int, dst: EngineShard,
                        keep_slots: int) -> None:
        """Drain pressure valve: shrink and migrate in one checkpoint —
        restore only the first ``keep_slots`` blocks on ``dst``."""
        job = src.rids.jobs[rid]
        from_chains = job.granted_chains
        with self._sub("admit.restore"):
            blocks = src.pool.checkpoint(rid)[:keep_slots]
            src.pool.release(rid)
            src.rids.free(rid)
            dst.rids.alloc(job)
            job.slots = dst.pool.restore(job.rid, blocks)
        job.home_shard = dst.index
        job.migrated_ticks.append(self.tick_count)
        self.migrations += 1
        self._record_shrink(job, from_chains)

    # -------------------------------------------- completion-deadline SLO
    def _truncate_job(self, job: ActiveJob, to_levels: int) -> None:
        """Ladder truncation in place: cut the job's remaining temperature
        levels so it finishes by its ``finish_deadline``.  Nothing about
        the chain state, RNG streams or any level's arithmetic changes —
        only where the ladder *ends* — so the trajectory up to the new end
        is prefix-exact with the untruncated run, and a standalone replay
        of the recorded ``truncate_events`` reproduces the terminal
        champion bit-exactly (``run_standalone(truncate_schedule=...)``).
        """
        limit = self._levels_limit(job)
        to_levels = int(to_levels)
        floor = max(int(job.req.min_levels), min(job.level, limit))
        to_levels = max(to_levels, floor)     # never below the SLO floor
        if to_levels >= limit:
            return                            # nothing to cut
        job.truncated_ticks.append(self.tick_count)
        job.truncate_events.append((job.level, limit, to_levels))
        job.levels_limit = to_levels
        self.truncations += 1
        tel = self.telemetry
        if tel.enabled:
            tel.decision(self.tick_count, "truncate",
                         req_id=job.req.req_id, shard=job.home_shard,
                         level=job.level, from_levels=limit,
                         to_levels=to_levels)
            if tel.trace is not None:
                tel.trace.request_instant(
                    job.req.req_id, "truncate", from_levels=limit,
                    to_levels=to_levels, tick=self.tick_count)

    def truncate_active(self, req_id: int, n_levels: int) -> bool:
        """Shorten the running request ``req_id``'s ladder to ``n_levels``
        total temperature levels — the operator/replay entry point for
        finish-deadline degrade; the scheduler's ``plan_truncations``
        drives the same path.  Clamped to the request's ``min_levels``
        floor.  Returns False if the request is not active or the cut
        would not shorten anything (already at/below that length)."""
        for _shard, job in self._iter_jobs():
            if job.req.req_id == req_id:
                before = self._levels_limit(job)
                self._truncate_job(job, n_levels)
                return self._levels_limit(job) < before
        return False

    def _plan_truncations(self) -> None:
        """Apply this boundary's finish-deadline truncations (scheduler
        plans, engine executes — like every other planner)."""
        views = [self._view(s) for s in self.shards]
        with self._pt("schedule"):
            plan = self.scheduler.plan_truncations(views, self.tick_count)
        with self._pt("admit"):
            for rid, si, to_levels in plan:
                self._truncate_job(self._shard(si).rids.jobs[rid],
                                   to_levels)

    def _evacuate_draining(self, budget: int) -> int:
        """Execute this tick's drain plan; returns actions performed."""
        with self._pt("schedule"):
            draining = [self._view(s) for s in self.shards if s.draining]
            survivors = [self._view(s) for s in self.live_shards]
            actions = self.scheduler.plan_evacuation(
                draining, survivors, self.cfg.chains_per_slot,
                self.tick_count, budget)
        with self._pt("admit"):
            for kind, rid, src, dst, width in actions:
                if kind == "migrate":
                    self._migrate_job(self._shard(src), rid,
                                      self._shard(dst))
                elif kind == "shrink":
                    self._shrink_migrate(self._shard(src), rid,
                                         self._shard(dst), width)
                else:
                    self._swap_out(self._shard(src), rid)
        return len(actions)

    def _retire_drained(self) -> None:
        """Remove empty draining shards from the fleet (their index is
        never reused; ``retired_shards`` records index and tick).  A
        retired shard's telemetry series survive it: per-shard metrics
        are labelled by the stable index in the registry, which is never
        pruned."""
        for shard in [s for s in self.shards
                      if s.draining and not s.rids.jobs]:
            self.shards.remove(shard)
            self.retired_shards.append((shard.index, self.tick_count))
            self.telemetry.decision(self.tick_count, "shard_retired",
                                    shard=shard.index)

    def drain(self, shard_index: int) -> None:
        """Begin draining shard ``shard_index`` for retirement.

        The shard takes no new placements; each tick its jobs are
        checkpoint-evacuated onto the surviving shards (bounded by
        ``migration_budget`` actions per tick, highest effective
        priority first — migrated whole when a survivor has room,
        shrunk into the roomiest survivor when degrade-eligible, swapped
        to the queue as the last resort) and it is retired — removed
        from the fleet — once empty.  Idempotent; raises if it would
        leave no live shard.  Every evacuated trajectory stays bit-exact
        (see docs/serving.md).
        """
        shard = self._shard(shard_index)
        if shard.draining:
            return
        if len(self.live_shards) <= 1:
            raise ValueError(
                "cannot drain the last live shard; resize up first")
        shard.draining = True
        self.telemetry.decision(self.tick_count, "drain", shard=shard_index,
                                resident_jobs=len(shard.rids.jobs))
        if not shard.rids.jobs:
            self._retire_drained()

    def add_shards(self, n: int) -> List[int]:
        """Grow the fleet by ``n`` fresh shards (``n_slots`` slots each,
        devices round-robin); returns their (new, never-reused) indices."""
        if n < 0:
            raise ValueError(f"n must be >= 0, got {n}")
        new = []
        for _ in range(n):
            idx = self._next_shard_index
            self._next_shard_index += 1
            self.shards.append(make_shard(
                idx, self.cfg.n_slots, self.cfg.chains_per_slot))
            new.append(idx)
            self.telemetry.decision(self.tick_count, "shard_added",
                                    shard=idx)
        return new

    def resize(self, n_devices: int) -> None:
        """Elastically resize the fleet to ``n_devices`` live shards.

        Growing first cancels in-progress drains (cheapest capacity:
        the shard is already populated), then adds fresh shards.
        Shrinking drains the emptiest live shards (fewest held slots,
        ties to the highest index) — they retire as evacuation
        completes, so the fleet passes through a transient
        ``n_live + n_draining`` state rather than dropping capacity
        instantaneously.
        """
        if n_devices < 1:
            raise ValueError(f"n_devices must be >= 1, got {n_devices}")
        live = self.live_shards
        if n_devices > len(live):
            grow = n_devices - len(live)
            for shard in sorted((s for s in self.shards if s.draining),
                                key=lambda s: s.index):
                if grow == 0:
                    break
                shard.draining = False      # cancel the drain: un-retire
                grow -= 1
            self.add_shards(grow)
        elif n_devices < len(live):
            doomed = sorted(live, key=lambda s: (s.pool.n_active, -s.index))
            for shard in doomed[:len(live) - n_devices]:
                self.drain(shard.index)

    def degrade_active(self, req_id: int, n_chains: int) -> bool:
        """Shrink the running request ``req_id`` to ``n_chains`` chains
        (rounded up to whole slots) — the operator/test entry point for
        proactive degrade at a chosen temperature level; the scheduler's
        ``plan_shrinks`` drives the same path.  Returns False if the
        request is not active, already at/below that width, or a
        parallel-tempering job (a PT job's width is its temperature-ladder
        resolution — truncating it mid-flight would change the method,
        not just the budget; the scheduler's planners skip PT too)."""
        slots_new = max(1, -(-n_chains // self.cfg.chains_per_slot))
        for shard, job in self._iter_jobs():
            if job.req.req_id == req_id:
                if slots_new >= len(job.slots) or job.req.method == "pt":
                    return False
                self._shrink_job(shard, job.rid, slots_new)
                return True
        return False

    def attach_controller(self, controller) -> None:
        """Attach a closed-loop controller (service/autoscaler.py): an
        object with ``maybe_sample(engine)`` — called at the top of every
        tick, before admission — and a ``next_sample_tick`` attribute so
        ``run_stream``'s idle fast-forward never leaps over a scheduled
        sampling tick (controller decisions are tick-aligned like
        scripted ops)."""
        self.controller = controller

    def schedule_op(self, tick: int, fn) -> None:
        """Run ``fn()`` at the start of the first tick >= ``tick`` —
        the hook ``serve_sa --drain-at/--resize`` uses to script fleet
        changes onto the deterministic tick axis."""
        self._ops.append((int(tick), self._op_seq, fn))
        self._op_seq += 1
        self._ops.sort(key=lambda op: op[:2])

    @property
    def _next_op_tick(self) -> float:
        return self._ops[0][0] if self._ops else float("inf")

    def _run_due_ops(self) -> None:
        while self._ops and self._ops[0][0] <= self.tick_count:
            _, _, fn = self._ops.pop(0)
            fn()

    def _reject(self, entry: QueueEntry) -> None:
        """SLO fast-fail: terminal 'rejected' result, no solution."""
        req = entry.req
        arrival, submit_wall = self._submit_info.pop(
            req.req_id, (float(entry.submit_tick), float("nan")))
        self.results.append(RequestResult(
            req_id=req.req_id, objective=req.objective, dim=req.dim,
            x_best=None, f_best=float("inf"), levels_run=0, n_evals=0,
            submit_tick=entry.submit_tick, start_tick=-1,
            finish_tick=self.tick_count, finish_reason="rejected",
            arrival_time=arrival, submit_wall=submit_wall,
            finish_wall=self._now(), requested_chains=req.n_chains,
            granted_chains=0, home_shard=-1))
        self.rejections += 1
        tel = self.telemetry
        if tel.enabled:
            tel.decision(self.tick_count, "reject", req_id=req.req_id,
                         waited=self.tick_count - entry.submit_tick)
            if tel.trace is not None:
                tel.trace.request_end(req.req_id, reason="rejected",
                                      tick=self.tick_count)

    # ---------------------------------------------------------------- tick
    def tick(self) -> None:
        """Admit, then advance every active slot by ``macro_k`` temperature
        levels in one fused dispatch per group (one level when K=1).

        Two passes over the shards: *launch* every ``(shard, dim, N)``
        group's device program first (JAX dispatch is asynchronous, so
        programs on different devices execute concurrently), then
        *collect* — read the champions back to host, fold them and retire
        finished requests; the chain state stays on the device.
        Collecting inline per group would serialize the shards:
        ``np.asarray`` blocks on the transfer, and device k+1 would not
        launch until device k had fully finished.

        Macro-ticks (K>1): the top of a tick is a **macro-tick boundary**
        — scripted ops, admission, preemption, migration and rebalancing
        all land here, then every group runs K ladder levels on device
        with per-level champion exchange (``_group_tick_fused``) before
        the next boundary.  ``tick_count`` stays on the *ladder-level*
        clock: an active macro-tick advances it by the most levels any
        job consumed (K mid-flight, less only when every job terminated
        inside the macro-tick; 1 per idle tick), so arrival timestamps,
        queue-delay and lifecycle latencies keep level units at any K.

        With telemetry enabled, each phase of the tick runs under a
        monotonic span (``schedule / admit / dispatch / device_wait /
        materialize / retire``), and an explicit ``block_until_ready``
        fence per shard separates host-side launch cost (``dispatch``)
        from device compute (``device_wait``) — at K>1 the fence simply
        covers the whole fused K-level program.  The fence changes *when*
        the host observes completion, never what was computed: the
        launch-all-then-collect order is preserved, so telemetry is
        bit-exact (tests assert it).
        """
        pt = self._pt
        self._run_due_ops()       # scripted drain/resize land tick-aligned
        if self.controller is not None:
            # Closed-loop control: the controller samples fleet signals
            # and may resize()/schedule_op() before this tick's admission
            # sees the fleet, so capacity changes land boundary-aligned
            # exactly like scripted ops.
            with self._pt("schedule"):
                self.controller.maybe_sample(self)
        for shard in self.shards:
            shard.resident_ticks += 1
            self.slot_ticks += shard.pool.n_slots
        self._admit()
        self._plan_truncations()  # finish-deadline cuts, boundary-aligned
        if self.telemetry.enabled:
            # A shard with a job launches its group; one without idles.
            for shard in self.live_shards:
                if not shard.rids.jobs:
                    self.telemetry.m_shard_idle_ticks.inc(1, str(shard.index))
        if self.n_active == 0:
            self._retire_drained()
            self._end_tick_telemetry()
            self.tick_count += 1
            return
        K = self.cfg.macro_k
        launches = []
        for shard in self.shards:
            # Dispatch groups are keyed by shape alone — (family, dim, N)
            # — because the objective id (or QAP instance operand) is a
            # runtime kernel input; mixed-objective groups share one
            # compiled program, and one program per *family* serves every
            # instance of that family.  Groups never span shards: each
            # runs on the shard's own device.
            groups: Dict[Tuple[str, int, int], List[ActiveJob]] = \
                defaultdict(list)
            for job in shard.rids.jobs.values():
                groups[(job.req.family, job.req.dim, job.req.N)].append(job)
            with pt("dispatch", shard.index):
                for (family, dim, n_steps), jobs in sorted(groups.items()):
                    launches.append(
                        self._launch_group(shard, family, dim, n_steps, jobs)
                        if K == 1 else
                        self._launch_group_fused(shard, family, dim,
                                                 n_steps, jobs))
                    self.group_launches += 1
                # A group that ran nothing this tick keeps no device buffer.
                for key in shard.group_cache.keys() - groups.keys():
                    del shard.group_cache[key]
        if self.telemetry.enabled:
            self.telemetry.m_launches.inc(len(launches))
            # Fence: wait for each shard's device arrays so device compute
            # lands in its own span instead of smearing into the first
            # np.asarray of the collect pass.  All programs are already
            # in flight, so waiting shard-by-shard keeps the overlap.
            for launch in launches:
                with pt("device_wait", launch[0].index):
                    jax.block_until_ready(launch[3])
        finished = []
        advance = 1
        for launch in launches:
            with pt("materialize", launch[0].index):
                if K == 1:
                    finished.extend(self._collect_group(*launch))
                else:
                    got, levels = self._collect_group_fused(*launch)
                    finished.extend(got)
                    advance = max(advance, levels)
        if advance > 1:
            # The macro-tick held the fleet's slots for `advance` ladder
            # levels (admission waits for the next boundary), so occupancy
            # bills that many slot-ticks per slot — `advance` is the max
            # levels any job actually consumed, < K only when every job
            # terminated inside this macro-tick (the clock must not run
            # past the last level anyone swept, or goodput/occupancy
            # denominators would drift off the K=1 axis).
            for shard in self.shards:
                shard.resident_ticks += advance - 1
                self.slot_ticks += shard.pool.n_slots * (advance - 1)
        with pt("retire"):
            for shard, job, reason, finish_tick in finished:
                self._retire(shard, job, reason, finish_tick=finish_tick)
        # A draining shard whose last job just retired (or evacuated) is
        # removed now, so a run that ends this tick leaves no zombie
        # shards behind.
        self._retire_drained()
        self._end_tick_telemetry(levels=advance)
        self.tick_count += advance

    def _end_tick_telemetry(self, levels: int = 1) -> None:
        """Drain this tick's spans and sub-spans into the registry / trace
        (no-op when telemetry is off).  ``levels`` is the ladder-level
        advance of this tick (K for an active macro-tick) so the tick
        counter metric stays on the level clock."""
        if self.telemetry.enabled:
            self.telemetry.end_tick(self.tick_count, self._pt, self._sub,
                                    self.shards, len(self.scheduler),
                                    self.n_active, levels=levels)

    def _collect_group(self, shard: EngineShard, n_steps: int,
                       jobs: List[ActiveJob], outs):
        """Fold one group's champions and advance its jobs one level;
        returns the finished ``(shard, job, reason, finish_tick)`` tuples
        for the caller's retire pass (slot frees can wait: admission
        happens at the top of the next tick, so deferring the release is
        equivalent)."""
        tel = self.telemetry
        sub = self._sub
        with sub("materialize.d2h", shard.index):
            # Only what the host folds: the chain state stays on the
            # device (the pool holds refs into outs[0], set at launch).
            xb, fb = np.asarray(outs[2]), np.asarray(outs[3])
            fxh = (np.asarray(outs[1])
                   if any(j.req.pa_ess_ratio > 0 for j in jobs) else None)
        finished = []
        row0 = 0
        with sub("materialize.fold", shard.index):
            for job in jobs:
                rows = slice(row0, row0 + job.granted_chains)
                row0 += job.granted_chains
                f = float(fb[job.rid])
                if f < job.best_f:
                    job.best_f = f
                    job.best_x = xb[job.rid].copy()
                if job.first_tick < 0:
                    job.first_tick = self.tick_count
                    job.first_tick_wall = self._now()
                self.sweeps_done += len(job.slots)
                shard.sweeps_done += len(job.slots)
                job.level += 1
                job.steps_done += n_steps
                job.evals += n_steps * job.granted_chains
                job.T *= job.req.rho
                job.history.append(job.best_f)   # champion trajectory/level
                if tel.enabled:
                    tel.tenant_slot_ticks(job.req.req_id, len(job.slots))
                reason = self._finish_reason(job)
                if reason is not None:
                    finished.append((shard, job, reason, self.tick_count))
                elif fxh is not None:
                    self._maybe_pa_shrink(shard, job, fxh[rows])
        return finished

    def _collect_group_fused(self, shard: EngineShard, n_steps: int,
                             jobs: List[ActiveJob], outs,
                             planned: Dict[int, int]):
        """Fold one fused macro-tick's results on host.

        Only the per-level champion stacks transfer to host (small); chain
        state stays device-resident — the pool already holds refs into
        ``outs[0]`` (set at launch).  Each job's levels are counted
        exactly as K=1 collects would: fold champion, advance the cursors,
        append history, check the finish reason — stopping at the first
        terminal level.  A target stop mid-macro-tick therefore truncates
        the job identically to the K=1 engine; the extra device levels it
        already swept are discarded with its slots at retire.  Budget and
        ladder stops cannot fire early: the launch planned at most that
        many levels.  ``finish_tick`` is the ladder-level clock value of
        the finishing level — boundary + counted − 1 — so lifecycle
        latencies keep level units at any K.

        Returns ``(finished, max_counted)``: the terminal tuples plus the
        most levels any job in this group consumed — the caller advances
        the tick clock by the fleet-wide max, keeping ``tick_count`` equal
        to the K=1 engine's at every boundary.
        """
        tel = self.telemetry
        boundary = self.tick_count
        with self._sub("materialize.d2h", shard.index):
            fb_all = np.asarray(outs[2])  # (K, num_segments) champion values
            xb_all = np.asarray(outs[3])  # (K, num_segments, dim) champions
            fxh = (np.asarray(outs[1])    # last-live-level post-exchange fx
                   if any(j.req.pa_ess_ratio > 0 for j in jobs) else None)
        finished = []
        max_counted = 1
        row0 = 0
        with self._sub("materialize.fold", shard.index):
            for job in jobs:
                rows = slice(row0, row0 + job.granted_chains)
                row0 += job.granted_chains
                if job.first_tick < 0:
                    job.first_tick = boundary
                    job.first_tick_wall = self._now()
                counted = 0
                reason = None
                for i in range(planned[job.rid]):
                    f = float(fb_all[i, job.rid])
                    if f < job.best_f:
                        job.best_f = f
                        job.best_x = xb_all[i, job.rid].copy()
                    counted += 1
                    self.sweeps_done += len(job.slots)
                    shard.sweeps_done += len(job.slots)
                    job.level += 1
                    job.steps_done += n_steps
                    job.evals += n_steps * job.granted_chains
                    job.T *= job.req.rho
                    job.history.append(job.best_f)   # champion per level
                    if tel.enabled:
                        tel.tenant_slot_ticks(job.req.req_id,
                                              len(job.slots))
                    reason = self._finish_reason(job)
                    if reason is not None:
                        break
                max_counted = max(max_counted, counted)
                if reason is not None:
                    finished.append((shard, job, reason,
                                     boundary + counted - 1))
                elif fxh is not None:
                    self._maybe_pa_shrink(shard, job, fxh[rows])
        return finished, max_counted

    def _pack_class_controls(self, jobs: List[ActiveJob], n_padded: int,
                             n_parities: int):
        """Per-chain workload-class arrays for one packed group.

        A request's chains are contiguous in the packed buffer in logical
        chain order (``slot_list`` enumerates each job's slots in grant
        order), so PT partner rows and PA segment ranges are just offsets
        from the job's first packed row.  Defaults are the identity for
        every stage of the composite exchange: plain code, self-partner,
        self-range — pad blocks and plain-SA tenants pass through bitwise
        untouched.  ``n_parities`` rows of partners are built (1 for the
        K=1 path, 2 for the fused path's even/odd alternation); row ``j``
        holds each chain's partner at the parity of its own job's
        ``level + j``.
        """
        cps = self.cfg.chains_per_slot
        nc = n_padded * cps
        rows = np.arange(nc, dtype=np.int32)
        mcode = np.zeros((nc,), np.int8)
        t_rung = np.ones((nc,), np.float32)
        partner = np.tile(rows, (n_parities, 1))
        pairlo = np.zeros((n_parities, nc), np.uint32)
        seg_lo = rows.copy()
        seg_hi = rows + 1
        row0 = 0
        for job in jobs:
            n = job.granted_chains
            mcode[row0:row0 + n] = _job_mcode(job.req)
            if job.req.method == "pt":
                t_rung[row0:row0 + n] = job.req.pt_rungs(n)
                for j in range(n_parities):
                    prt, plo = _pt_partners(n, (job.level + j) % 2)
                    partner[j, row0:row0 + n] = row0 + prt
                    pairlo[j, row0:row0 + n] = plo
            elif job.req.method == "pa":
                seg_lo[row0:row0 + n] = row0
                seg_hi[row0:row0 + n] = row0 + n
            row0 += n
        return mcode, t_rung, partner, pairlo, seg_lo, seg_hi

    def _launch_group_fused(self, shard: EngineShard, family: str, dim: int,
                            n_steps: int, jobs: List[ActiveJob]):
        """Pack the group's controls, reuse (or rebuild) its device state
        buffer, and launch one fused K-level program (async).

        ``family`` picks the device program and the packing details: the
        continuous Metropolis program takes per-block objective ids and PA
        increments; the permutation (QAP) program takes per-block
        flow/distance operands and int32 chain state.  Everything else —
        level planning, control layout, the double buffer, the collect
        contract — is family-agnostic.

        Per-job level planning: ``min(K, remaining ladder, remaining eval
        budget)`` — computed on host so budget/ladder finishes land on
        exactly the K=1 level, never overshooting.  Temperatures for the
        K levels are iterated in float64 on host (``t *= rho``, matching
        the K=1 cursor update) and threaded as a ``(K, n_blocks)`` SMEM
        array.

        The chain state comes from :meth:`_group_state` (the double
        buffer, shared with the K=1 path) and stays on the device after
        the launch (:meth:`_keep_group_state`).
        """
        cps = self.cfg.chains_per_slot
        K = self.cfg.macro_k
        is_qap = family == fam_mod.FAMILY_PERMUTATION
        tel = self.telemetry
        sub = self._sub
        slot_list: List[Tuple[int, ActiveJob]] = [
            (s, job) for job in jobs for s in job.slots]
        n_blocks = len(slot_list)
        n_padded = 1
        while n_padded < n_blocks:
            n_padded *= 2

        with sub("dispatch.pack", shard.index):
            planned: Dict[int, int] = {}
            for job in jobs:
                p = min(K, max(1, self._levels_limit(job) - job.level))
                if job.req.max_evals is not None:
                    per_level = max(1, n_steps * job.granted_chains)
                    remaining = job.req.max_evals - job.evals
                    p = min(p, max(1, -(-remaining // per_level)))
                planned[job.rid] = p

            kid_blk = np.empty((n_padded,), np.int32)
            if is_qap:
                # Per-block instance operands, packed (n_padded * dim,
                # dim): block b reads rows [b*dim, (b+1)*dim).  Runtime
                # inputs, so mixed instances co-batch without recompiling.
                F_blk = np.empty((n_padded * dim, dim), np.float32)
                D_blk = np.empty((n_padded * dim, dim), np.float32)
            T_lvls = np.empty((K, n_padded), np.float32)
            dbeta_lvls = np.zeros((K, n_padded), np.float32)
            seed_blk = np.empty((n_padded,), np.uint32)
            step0_blk = np.empty((n_padded,), np.uint32)
            base_blk = np.empty((n_padded,), np.uint32)
            levels_blk = np.empty((n_padded,), np.int32)
            lvl0_blk = np.zeros((n_padded,), np.uint32)
            seg = np.empty((n_padded * cps,), np.int32)
            adopt = np.empty((n_padded * cps,), bool)
            for b, (s, job) in enumerate(slot_list):
                kid_blk[b] = np.int32(job.req.kid)
                if is_qap:
                    inst = job.req.instance
                    F_blk[b * dim:(b + 1) * dim] = inst.F
                    D_blk[b * dim:(b + 1) * dim] = inst.D
                is_pa = job.req.method == "pa"
                t = job.T
                for i in range(K):
                    # float64 iteration, f32 per level — identical to
                    # K=1's pack-then-advance of the float ``job.T`` cursor.
                    T_lvls[i, b] = t
                    if is_pa:
                        dbeta_lvls[i, b] = _pa_dbeta(t, job.req.rho)
                    t *= job.req.rho
                seed_blk[b] = np.uint32(job.req.seed)
                step0_blk[b] = np.uint32(job.steps_done)
                base_blk[b] = shard.pool.chain_base[s]
                levels_blk[b] = planned[job.rid]
                lvl0_blk[b] = np.uint32(job.level)
                seg[b * cps:(b + 1) * cps] = job.rid
                adopt[b * cps:(b + 1) * cps] = (
                    job.req.method == "sa" and job.req.exchange == "sync")
            for b in range(n_blocks, n_padded):
                # Pad blocks are *dead* (zero planned levels): pure
                # pass-through, so whatever a reused buffer holds in its
                # pad rows is legal — they cost lanes, not correctness.
                kid_blk[b] = kid_blk[0]
                if is_qap:
                    F_blk[b * dim:(b + 1) * dim] = F_blk[:dim]
                    D_blk[b * dim:(b + 1) * dim] = D_blk[:dim]
                T_lvls[:, b] = T_lvls[:, 0]
                seed_blk[b] = seed_blk[0]
                step0_blk[b] = step0_blk[0]
                base_blk[b] = base_blk[0]
                levels_blk[b] = 0
                seg[b * cps:(b + 1) * cps] = self.cfg.n_slots
                adopt[b * cps:(b + 1) * cps] = False
            mcode, t_rung, partner2, pairlo2, seg_lo, seg_hi = \
                self._pack_class_controls(jobs, n_padded, 2)

        x_dev = self._group_state(shard, (family, dim, n_steps), slot_list,
                                  n_padded)
        with sub("dispatch.h2d", shard.index):
            # One batched transfer for all control arrays: separate
            # device_put dispatches were the dominant per-launch host cost
            # once the state buffer started cache-hitting.
            if is_qap:
                ctrl = jax.device_put(
                    (F_blk, D_blk, T_lvls, seed_blk, step0_blk, base_blk,
                     levels_blk, lvl0_blk, seg, adopt, mcode, t_rung,
                     partner2, pairlo2, seg_lo, seg_hi), shard.device)
            else:
                ctrl = jax.device_put(
                    (kid_blk, T_lvls, seed_blk, step0_blk, base_blk,
                     levels_blk, lvl0_blk, dbeta_lvls, seg, adopt, mcode,
                     t_rung, partner2, pairlo2, seg_lo, seg_hi), shard.device)
        with sub("dispatch.launch", shard.index):
            if is_qap:
                outs = _group_tick_qap_fused(
                    x_dev, *ctrl,
                    k=K, n_steps=n_steps, blk=cps,
                    use_pallas=self._use_pallas,
                    interpret=self.cfg.interpret,
                    num_segments=self.cfg.n_slots + 1)
            else:
                outs = _group_tick_fused(
                    x_dev, *ctrl,
                    k=K, n_steps=n_steps, blk=cps, variant=self.cfg.variant,
                    use_pallas=self._use_pallas,
                    interpret=self.cfg.interpret,
                    num_segments=self.cfg.n_slots + 1)
        if tel.enabled:
            live = sum(planned[job.rid] * len(job.slots) for job in jobs)
            tel.m_block_steps.inc(live * n_steps, "live")
            tel.m_block_steps.inc((n_blocks * K - live) * n_steps, "dead")
            tel.m_block_steps.inc((n_padded - n_blocks) * K * n_steps,
                                  "padded")
        self._keep_group_state(shard, (family, dim, n_steps), slot_list,
                               n_padded, outs[0])
        return shard, n_steps, jobs, outs, planned

    def _launch_group(self, shard: EngineShard, family: str, dim: int,
                      n_steps: int, jobs: List[ActiveJob]):
        """Pack the group's controls, reuse (or rebuild) its device state
        buffer, and launch its one-level device program (async); returns
        the collect-pass arguments.  ``family`` picks the program
        (Metropolis vs QAP pairwise-exchange) and the state dtype; see
        :meth:`_launch_group_fused`."""
        cps = self.cfg.chains_per_slot
        is_qap = family == fam_mod.FAMILY_PERMUTATION
        slot_list: List[Tuple[int, ActiveJob]] = [
            (s, job) for job in jobs for s in job.slots]
        n_blocks = len(slot_list)
        # Pad to a power of two of blocks so the number of compiled
        # signatures per (family, dim, N) is O(log n_slots), not
        # O(n_slots).
        n_padded = 1
        while n_padded < n_blocks:
            n_padded *= 2

        tel = self.telemetry
        sub = self._sub
        with sub("dispatch.pack", shard.index):
            kid_blk = np.empty((n_padded,), np.int32)
            if is_qap:
                F_blk = np.empty((n_padded * dim, dim), np.float32)
                D_blk = np.empty((n_padded * dim, dim), np.float32)
            T_blk = np.empty((n_padded,), np.float32)
            dbeta_blk = np.zeros((n_padded,), np.float32)
            seed_blk = np.empty((n_padded,), np.uint32)
            step0_blk = np.empty((n_padded,), np.uint32)
            base_blk = np.empty((n_padded,), np.uint32)
            lvl0_blk = np.zeros((n_padded,), np.uint32)
            seg = np.empty((n_padded * cps,), np.int32)
            adopt = np.empty((n_padded * cps,), bool)
            for b, (s, job) in enumerate(slot_list):
                kid_blk[b] = np.int32(job.req.kid)
                if is_qap:
                    inst = job.req.instance
                    F_blk[b * dim:(b + 1) * dim] = inst.F
                    D_blk[b * dim:(b + 1) * dim] = inst.D
                T_blk[b] = job.T
                if job.req.method == "pa":
                    dbeta_blk[b] = _pa_dbeta(job.T, job.req.rho)
                seed_blk[b] = np.uint32(job.req.seed)
                step0_blk[b] = np.uint32(job.steps_done)
                base_blk[b] = shard.pool.chain_base[s]
                lvl0_blk[b] = np.uint32(job.level)
                seg[b * cps:(b + 1) * cps] = job.rid
                adopt[b * cps:(b + 1) * cps] = (
                    job.req.method == "sa" and job.req.exchange == "sync")
            # Pad blocks run block 0's controls, claim the reserved
            # segment n_slots and never adopt: whatever state their rows
            # hold (a copy of block 0 when packed, a reused buffer's old
            # rows otherwise) costs lanes, not correctness.
            for b in range(n_blocks, n_padded):
                kid_blk[b] = kid_blk[0]
                if is_qap:
                    F_blk[b * dim:(b + 1) * dim] = F_blk[:dim]
                    D_blk[b * dim:(b + 1) * dim] = D_blk[:dim]
                T_blk[b] = T_blk[0]
                seed_blk[b] = seed_blk[0]
                step0_blk[b] = step0_blk[0]
                base_blk[b] = base_blk[0]
                seg[b * cps:(b + 1) * cps] = self.cfg.n_slots
                adopt[b * cps:(b + 1) * cps] = False
            mcode, t_rung, partner, pairlo, seg_lo, seg_hi = \
                self._pack_class_controls(jobs, n_padded, 1)

        # Committed transfers pin the group's program to the shard's mesh
        # device.  The call returns device arrays without blocking; the
        # collect pass reads the champions after every shard has launched.
        x_dev = self._group_state(shard, (family, dim, n_steps), slot_list,
                                  n_padded)
        with sub("dispatch.h2d", shard.index):
            if is_qap:
                ctrl = jax.device_put(
                    (F_blk, D_blk, T_blk, seed_blk, step0_blk, base_blk,
                     lvl0_blk, seg, adopt, mcode, t_rung, partner[0],
                     pairlo[0], seg_lo, seg_hi), shard.device)
            else:
                ctrl = jax.device_put(
                    (kid_blk, T_blk, seed_blk, step0_blk, base_blk, lvl0_blk,
                     dbeta_blk, seg, adopt, mcode, t_rung, partner[0],
                     pairlo[0], seg_lo, seg_hi), shard.device)
        with sub("dispatch.launch", shard.index):
            if is_qap:
                outs = _group_tick_qap(
                    x_dev, *ctrl, n_steps=n_steps, blk=cps,
                    use_pallas=self._use_pallas,
                    interpret=self.cfg.interpret,
                    num_segments=self.cfg.n_slots + 1)
            else:
                outs = _group_tick(
                    x_dev, *ctrl, n_steps=n_steps, blk=cps,
                    variant=self.cfg.variant, use_pallas=self._use_pallas,
                    interpret=self.cfg.interpret,
                    num_segments=self.cfg.n_slots + 1)
        if tel.enabled:
            tel.m_block_steps.inc(n_blocks * n_steps, "live")
            tel.m_block_steps.inc((n_padded - n_blocks) * n_steps, "padded")
        self._keep_group_state(shard, (family, dim, n_steps), slot_list,
                               n_padded, outs[0])
        return shard, n_steps, jobs, outs

    def _group_state(self, shard: EngineShard, key: Tuple[str, int, int],
                     slot_list: List[Tuple[int, ActiveJob]], n_padded: int):
        """The group's packed chain state on the shard's device, for a
        launch that donates it (both launch paths).

        The double buffer: if every slot of the group still references
        this group's cached output buffer at its packed rows, with the same
        padding — membership, order and content unchanged since the last
        launch — the host repack and transfer are skipped and the cached
        buffer goes straight back in.  Any admit/checkpoint/migrate/shrink/
        retire in between breaks the signature and falls back to a host
        repack from the pool (``get_block`` reads each device buffer back
        once) and an upload.
        """
        family, dim, _ = key
        cps = self.cfg.chains_per_slot
        pool = shard.pool
        tel = self.telemetry
        with self._sub("dispatch.pack", shard.index):
            cache = shard.group_cache.get(key)
            if cache is not None and cache["n_padded"] == n_padded:
                buf = cache["buf"]
                for b, (s, _job) in enumerate(slot_list):
                    ref = pool.device_ref(s)
                    if (ref is None or ref.buf is not buf
                            or ref.start != b * cps):
                        break
                else:
                    if tel.enabled:
                        tel.m_state_buffer.inc(1, "hit")
                    return buf
            read0 = pool.bytes_read
            x = np.empty((n_padded * cps, dim),
                         np.int32 if family == fam_mod.FAMILY_PERMUTATION
                         else np.float32)
            for b, (s, _job) in enumerate(slot_list):
                x[b * cps:(b + 1) * cps] = pool.get_block(s)
            for b in range(len(slot_list), n_padded):
                x[b * cps:(b + 1) * cps] = x[:cps]
        with self._sub("dispatch.h2d", shard.index):
            x_dev = jax.device_put(x, shard.device)
        if tel.enabled:
            tel.m_state_buffer.inc(1, "repack")
            tel.m_state_bytes.inc(pool.bytes_read - read0, "d2h")
            tel.m_state_bytes.inc(x.nbytes, "h2d")
        return x_dev

    @staticmethod
    def _keep_group_state(shard: EngineShard, key: Tuple[str, int, int],
                          slot_list: List[Tuple[int, ActiveJob]],
                          n_padded: int, out_x) -> None:
        """The group's state now lives in the launch's output buffer.
        Point every slot there (lazily — materialized only by checkpoint/
        migrate/shrink or a cache-miss repack) and arm the double buffer
        for the next launch.  The donated input has no readers left: every
        ref into it was just replaced."""
        shard.pool.set_device_blocks([s for s, _job in slot_list], out_x)
        shard.group_cache[key] = {"buf": out_x, "n_padded": n_padded}

    def _finish_reason(self, job: ActiveJob) -> Optional[str]:
        req = job.req
        if req.target_error is not None:
            # submit() guarantees the optimum exists; .get keeps the tick
            # loop un-wedgeable even if F_OPT is mutated under a live job.
            # Permutation requests read the instance's best_known instead.
            f_opt = (F_OPT.get(req.kid)
                     if req.family == fam_mod.FAMILY_CONTINUOUS
                     else req.f_opt)
            if f_opt is not None and job.best_f <= f_opt + req.target_error:
                return "target"
        if req.max_evals is not None and job.evals >= req.max_evals:
            return "budget"
        if job.level >= self._levels_limit(job):
            # 'truncated' only when the finish-deadline degrade actually
            # cut the ladder — a full-length finish stays 'ladder' even
            # for requests that carried a finish_deadline.
            return "truncated" if job.truncate_events else "ladder"
        return None

    @staticmethod
    def _levels_limit(job: ActiveJob) -> int:
        """The job's effective ladder length: ``levels_limit`` once placed
        (only ever cut, never below ``req.min_levels``), falling back to
        the request's full ladder for jobs that predate placement."""
        return job.levels_limit or job.req.n_levels

    def _retire(self, shard: EngineShard, job: ActiveJob, reason: str,
                finish_tick: Optional[int] = None) -> None:
        # finish_tick is on the ladder-level clock: the K=1 path passes
        # the current tick; the fused path passes boundary + counted - 1
        # (the level at which the finish reason actually fired).
        if finish_tick is None:
            finish_tick = self.tick_count
        self.results.append(RequestResult(
            req_id=job.req.req_id, objective=job.req.objective,
            dim=job.req.dim, x_best=job.best_x, f_best=job.best_f,
            levels_run=job.level, n_evals=job.evals,
            submit_tick=job.submit_tick, start_tick=job.start_tick,
            finish_tick=finish_tick, finish_reason=reason,
            arrival_time=job.arrival_time, first_tick=job.first_tick,
            submit_wall=job.submit_wall, admit_wall=job.admit_wall,
            first_tick_wall=job.first_tick_wall, finish_wall=self._now(),
            requested_chains=job.req.n_chains,
            granted_chains=job.granted_chains,
            preempted_ticks=list(job.preempted_ticks),
            resumed_ticks=list(job.resumed_ticks),
            champion_history=list(job.history),
            home_shard=job.home_shard,
            migrated_ticks=list(job.migrated_ticks),
            shrunk_ticks=list(job.shrunk_ticks),
            shrink_events=list(job.shrink_events),
            pa_shrink_events=list(job.pa_shrink_events),
            truncated_ticks=list(job.truncated_ticks),
            truncate_events=list(job.truncate_events)))
        shard.pool.release(job.rid)
        shard.rids.free(job.rid)
        tel = self.telemetry
        if tel.enabled:
            tel.decision(self.tick_count, "retire", req_id=job.req.req_id,
                         shard=shard.index, reason=reason, level=job.level,
                         best_f=job.best_f)
            if tel.trace is not None:
                tel.trace.request_end(job.req.req_id, reason=reason,
                                      tick=self.tick_count,
                                      levels=job.level, best_f=job.best_f)

    # ----------------------------------------------------------------- run
    def run(self, max_ticks: Optional[int] = None) -> List[RequestResult]:
        """Drive ticks until queue and pool drain (or ``max_ticks``).

        Closed-loop: serves whatever was already :meth:`submit`-ted — the
        degenerate open-loop run with an empty (exhausted) arrival stream.
        """
        from repro.service.arrivals import ArrivalProcess
        return self.run_stream(ArrivalProcess.batch([]), max_ticks=max_ticks)

    def run_stream(self, arrivals, max_ticks: Optional[int] = None
                   ) -> List[RequestResult]:
        """Open-loop serving: admit from an arrival process while ticking.

        ``arrivals`` is an :class:`~repro.service.arrivals.ArrivalProcess`
        (or anything with ``due(now)`` / ``exhausted``).  Each tick first
        submits every request whose arrival time has come due, then
        advances all in-flight work one temperature level; idle ticks (no
        active jobs, next arrival in the future) still advance the clock,
        so arrival timestamps stay on the tick axis.  Per-request
        lifecycle events (submit/admit/first-tick/complete) are stamped in
        both tick-time (deterministic under a fixed arrival seed) and
        wall-time — the latter on the engine's monotonic epoch, the same
        clock ``wall_s`` is measured on.
        """
        t0 = self._now()
        while True:
            if max_ticks is not None and self.tick_count >= max_ticks:
                break
            for t_arr, req in arrivals.due(self.tick_count):
                self.submit(req, arrival_time=t_arr)
            if self.done:
                if arrivals.exhausted:
                    break
                # Idle: fast-forward the clock to the next arrival instead
                # of spinning empty ticks (low offered load would otherwise
                # execute one no-op tick per time unit).  ceil() lands on
                # the first tick >= next_time — identical tick-axis
                # semantics to ticking through, since due(t) is <=-t.
                # Sources without next_time just tick through idle time.
                nxt = getattr(arrivals, "next_time", None)
                if nxt is not None and math.isfinite(nxt):
                    jump = int(math.ceil(nxt))
                    if max_ticks is not None:
                        jump = min(jump, max_ticks)
                    if self._ops:
                        # A scripted drain/resize must land on its exact
                        # tick, not be leapt over.
                        jump = min(jump, int(self._next_op_tick))
                    if self.controller is not None:
                        # Same for the controller's next sampling tick:
                        # idle gaps are exactly when scale-down decisions
                        # fire, so fast-forwarding past a sample would
                        # skip it (hysteresis windows would never elapse
                        # on a sparse trace).  A sample due now or earlier
                        # caps the jump at/below tick_count, falling
                        # through to tick() where the controller fires.
                        jump = min(jump,
                                   int(self.controller.next_sample_tick))
                    if jump > self.tick_count:
                        # Idle time still counts against occupancy: the
                        # fleet held its slots across the jumped ticks.
                        delta = jump - self.tick_count
                        for shard in self.shards:
                            shard.resident_ticks += delta
                            self.slot_ticks += delta * shard.pool.n_slots
                        self.tick_count = jump
                        continue
            self.tick()
        self.wall_s = self._now() - t0
        return self.results

    def stats(self) -> dict:
        wall = getattr(self, "wall_s", float("nan"))
        evals = sum(r.n_evals for r in self.results)

        def per_s(v):
            return v / wall if wall and wall > 0 else 0.0

        return {
            "ticks": self.tick_count,
            "devices": len(self.shards),
            "draining": sum(s.draining for s in self.shards),
            "shards_retired": len(self.retired_shards),
            "group_launches": self.group_launches,
            "submitted": self.n_submitted,
            "completed": sum(r.completed for r in self.results),
            "rejected": self.rejections,
            "preemptions": self.preemptions,
            "migrations": self.migrations,
            "shrinks": self.shrinks,
            "truncations": self.truncations,
            "sweeps": self.sweeps_done,
            # The fleet is elastic, so the occupancy denominator is the
            # accumulated slot-tick product, not ticks x a fixed slot
            # count (they agree exactly for a static fleet).
            "occupancy": self.sweeps_done / max(self.slot_ticks, 1),
            "shard_occupancy": [s.occupancy() for s in self.shards],
            "wall_s": wall,
            "requests_per_s": per_s(len(self.results)),
            "sweeps_per_s": per_s(self.sweeps_done),
            "chain_steps_per_s": per_s(evals),
            # Cumulative per-phase wall seconds (empty unless telemetry
            # was enabled): aggregate and per shard (retired shards too).
            "phases": self._phase_stats(),
        }

    def _phase_stats(self) -> dict:
        if not self.telemetry.enabled:
            return {}
        hist = self.telemetry.m_tick_phase
        agg = {phase: hist.summary(phase)
               for (phase,) in sorted(hist.series)}
        # Every shard the fleet ever had, retired ones included: the
        # counter's series are never pruned.
        per_shard: Dict[str, Dict[str, float]] = {}
        for (shard, phase), secs in sorted(
                self.telemetry.m_shard_phase.series.items(),
                key=lambda kv: (int(kv[0][0]), kv[0][1])):
            per_shard.setdefault(shard, {})[phase] = secs
        cpu = {phase: secs for (phase,), secs
               in sorted(self.telemetry.m_phase_cpu.series.items())}
        return {"aggregate": agg, "per_shard": per_shard,
                "cpu_seconds": cpu}


def run_standalone(req: SARequest, cfg: EngineConfig,
                   shrink_schedule=None,
                   truncate_schedule=None) -> RequestResult:
    """Serve ``req`` alone on a dedicated single-device pool — the
    per-tenant baseline.

    Placement-invariant RNG + segmented exchange make the packed engine
    produce the *same* trajectory as this single-tenant run (bit-exact
    champions for identical seeds) — on any home shard, across preemption
    and across cross-shard migration; tests assert it, serve_sa --check
    reports it.

    ``shrink_schedule`` replays proactive degrade: ``(level, n_chains)``
    pairs, applied in order once the job has completed ``level``
    temperature levels (``RequestResult.shrink_events`` records exactly
    this, as ``(level, from, to)``).  A job shrunk mid-flight by drain or
    overload pressure is bit-exact versus this standalone run of the
    same width schedule — the shrink itself (checkpoint, restore,
    placement, co-tenants) perturbs nothing; only the logical width
    trajectory matters.

    ``truncate_schedule`` replays finish-deadline ladder truncation the
    same way on the *level* axis: ``(level, n_levels)`` pairs, applied in
    order once the job has completed ``level`` temperature levels
    (``RequestResult.truncate_events`` records exactly this, as
    ``(level, from, to)``).  Truncation moves only where the ladder ends
    — no level's arithmetic changes — so the truncated run's champion is
    bit-exact with this replay (and prefix-exact with the untruncated
    run at every surviving level).

    The replay applies pending shrinks and truncations at macro-tick
    boundaries, so at ``cfg.macro_k > 1`` the schedules' levels must be
    K-aligned — which engine-recorded ``shrink_events`` and
    ``truncate_events`` always are, because the engine only cuts at
    boundaries and mid-flight jobs run exactly K levels per macro-tick.
    """
    alone = SAServeEngine(dataclasses.replace(
        cfg, n_slots=req.slots_needed(cfg.chains_per_slot), n_devices=1))
    alone.submit(req)
    if not shrink_schedule and not truncate_schedule:
        return alone.run()[0]
    pending = sorted((int(lvl), int(chains))
                     for lvl, chains in (shrink_schedule or ()))
    cuts = sorted((int(lvl), int(levels))
                  for lvl, levels in (truncate_schedule or ()))
    guard = 0
    while not alone.done:
        guard += 1
        assert guard < 100000, "standalone replay failed to drain"
        job = next((j for _, j in alone._iter_jobs()), None)
        while pending and job is not None and job.level >= pending[0][0]:
            alone.degrade_active(req.req_id, pending[0][1])
            pending.pop(0)
        while cuts and job is not None and job.level >= cuts[0][0]:
            alone.truncate_active(req.req_id, cuts[0][1])
            cuts.pop(0)
        alone.tick()
    return alone.results[0]
