"""CLI driver for the multi-tenant SA serving engine.

Generates a deterministic heterogeneous request mix (all four registry
objectives, several dims, several cooling schedules and priorities) and
serves it through the continuous-batching engine — either closed-loop
(the whole queue up front) or open-loop (``--arrivals poisson``: requests
stream in on a seeded Poisson timeline and queueing delay / time-to-first-
tick percentiles are reported).  With ``--check`` every request's champion
is compared against its standalone single-tenant run (placement invariance
makes them bit-exact); with ``--json`` the full per-request lifecycle
(tick-time and wall-time latencies) is emitted as one JSON document.

Usage::

  PYTHONPATH=src python -m repro.service.serve_sa --requests 32 --slots 8
  PYTHONPATH=src python -m repro.service.serve_sa --requests 8 --slots 4 \
      --chains-per-slot 16 --no-check        # quick smoke
  PYTHONPATH=src python -m repro.service.serve_sa --arrivals poisson \
      --rate 0.5 --requests 16 --slots 4 --chains-per-slot 16 --json
  XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
      python -m repro.service.serve_sa --devices 4 --slots 2 \
      --chains-per-slot 16 --arrivals poisson --rate 1.0   # sharded pool
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.service.arrivals import ArrivalProcess, latency_summary
from repro.service.engine import (EngineConfig, SAServeEngine, run_standalone)
from repro.service.request import SARequest
from repro.service.scheduler import SchedulerConfig

#: The synthetic-load mix: (objective, dim) pairs cycled over, crossed with
#: a few cooling schedules — ≥3 objectives, ≥2 dims/schedules by design.
#: Spans the full registry (including the PR-5 exponential/salomon growth:
#: runtime kid dispatch serves them with zero new compiled programs).
MIX_PROBLEMS = [
    ("rastrigin", 8), ("ackley", 16), ("schwefel", 8), ("griewank", 32),
    ("exponential", 16), ("salomon", 8),
    ("rastrigin", 32), ("ackley", 8), ("schwefel", 16), ("griewank", 16),
]
MIX_SCHEDULES = [
    dict(T0=100.0, T_min=0.5, rho=0.85, N=40),
    dict(T0=50.0, T_min=0.2, rho=0.90, N=25),
    dict(T0=200.0, T_min=1.0, rho=0.80, N=60),
]
#: Permutation-family (QAP) load: built-in instances with their sizes, and
#: cooling schedules scaled to typical swap-move delta magnitudes (tens,
#: not thousands — QAP costs move by O(F*D) per exchange).
MIX_QAP_PROBLEMS = [("grid12", 12), ("syn10", 10)]
MIX_QAP_SCHEDULES = [
    dict(T0=50.0, T_min=0.5, rho=0.90, N=25),
    dict(T0=30.0, T_min=0.3, rho=0.88, N=20),
]

_EPILOG = """\
flag groups:
  load shape      --requests (mix size), --max-slots-per-req (request
                  footprint), --seed (mix generator: objectives, dims,
                  schedules, priorities are all derived from it),
                  --method sa | pt | pa | mixed (workload class of the
                  mix; 'mixed' rotates all three through the same slot
                  pool — see the workload-class section of
                  docs/serving.md),
                  --family continuous | qap | mixed (problem
                  representation of the mix: float32 coordinate states,
                  int32 QAP permutations, or both alternating in one
                  pool — see the problem-family section of
                  docs/serving.md).
  pool shape      --slots (pool size PER SHARD), --chains-per-slot (kernel
                  block size; multiple of 8 on TPU), --variant (delta =
                  O(1) incremental evaluation, full = paper-faithful
                  O(dim)), --devices (engine shards on the 1-D (pool,)
                  mesh: each shard owns --slots slots on its own device
                  and dispatches independent device programs; the
                  scheduler homes each request on the least-loaded shard
                  and rebalances by bit-exact cross-shard migration.  On
                  CPU, XLA_FLAGS=--xla_force_host_platform_device_count=N
                  provides N real host devices; with fewer physical
                  devices, logical shards share them round-robin),
                  --macro-k (temperature levels fused into one device
                  dispatch: K > 1 amortizes the host's per-launch pack /
                  transfer / collect cost over K ladder levels and keeps
                  chain state device-resident between launches via
                  donated double buffers.  Scheduling decisions land on
                  macro-tick boundaries only; the tick clock stays in
                  ladder-level units and every trajectory stays bit-exact
                  at any K — --check passes unchanged).
  admission       --policy priority (aged, default) | fifo.
  overload / SLO  --overload-policy none (default) | reject (drop a
                  request once it queues past --deadline ticks) | degrade
                  (admit with fewer chains when the pool is short, floor =
                  one slot, with the --deadline reject backstop) | preempt
                  (swap out the lowest-effective-priority active jobs —
                  bounded by --preemption-budget per tick — to admit an
                  urgent arrival; swapped jobs resume bit-exactly).
                  Per-request classes can override via SARequest.on_overload.
  arrivals        --arrivals batch (closed-loop, everything at t=0,
                  default) | poisson (open-loop at --rate requests/tick,
                  seeded by --arrival-seed — deterministic timeline) |
                  bursty (groups of --burst requests arrive together at
                  the same mean rate — the overload stressor) | diurnal
                  (sinusoidal intensity around --rate with --period /
                  --amplitude: the autoscaler's day/night envelope).
                  --max-ticks bounds the run either way.
  control plane   --autoscale attaches the closed-loop controller
                  (service/autoscaler.py): it samples backlog, occupancy
                  and completion-deadline headroom every
                  --scale-sample-every ticks and resizes the fleet
                  within [--min-shards, --max-shards] — scale-up before
                  predicted SLO misses (x--scale-headroom safety),
                  scale-down one shard after --scale-window consecutive
                  sub---scale-low-util samples, at most one change per
                  --scale-cooldown ticks.  --finish-deadline-factor F
                  attaches completion SLOs to the mix (finish within
                  F x ladder-length ticks of arrival); the scheduler
                  meets them by ladder truncation, never cutting below
                  --min-levels-frac x ladder.  Truncated runs replay
                  bit-exactly under --check (the truncation schedule is
                  re-applied standalone, like shrink schedules).
  elastic fleet   --drain-at T (drain one shard at tick T: no new
                  placements, jobs checkpoint-evacuate onto survivors,
                  shard retires once empty; --drain-shard picks which,
                  default the highest-index live shard), --resize T:N
                  (repeatable: resize the fleet to N live shards at tick
                  T, composing drain/add), --high/--low-watermark
                  (background rebalancing: move narrow jobs off shards
                  above high onto shards below low, hysteresis built in),
                  --proactive-degrade (+ --shrink-budget): shrink
                  *running* degrade-class jobs down to their min-chains
                  floor when the queue head fits nowhere.  All of these
                  reuse the bit-exact checkpoint/restore, so --check
                  still holds (shrunk jobs are replayed standalone with
                  the same width schedule).
  reporting       --check (default) re-runs every request standalone and
                  exits 1 unless all champions are bit-exact — the
                  placement-invariance oracle; --no-check skips it.
                  --json replaces the human report with one JSON document:
                  config, engine stats, p50/p99 queueing delay +
                  time-to-first-tick + latency (tick clock, deterministic
                  under fixed seeds) and per-request lifecycle records
                  (plus wall-clock latencies for operators).

  observability   --trace out.json (Chrome/Perfetto trace_event timeline:
                  per-phase tick spans per shard + request lifecycle
                  tracks), --events out.jsonl (deterministic scheduler-
                  decision log, byte-identical under fixed seeds),
                  --metrics out.prom (Prometheus text exposition).  Any
                  of the three enables the telemetry bundle: per-phase
                  tick timing with block_until_ready fencing, streaming
                  p50/p90/p99, and a metrics snapshot in --json.  Off by
                  default — zero overhead, and provably bit-exact when
                  on (--check passes either way).  See
                  docs/observability.md.

The tick clock is the engine's native time axis, measured in ladder
levels: one macro-tick advances it by --macro-k (one level per active
slot per unit).  Latency percentiles are therefore comparable across K.
See docs/serving.md.
"""


def make_mix(n_requests: int, chains_per_slot: int, seed: int = 0,
             max_slots_per_req: int = 2, method: str = "sa",
             family: str = "continuous",
             finish_deadline_factor: float = None,
             min_levels_frac: float = 0.5) -> list:
    """Deterministic heterogeneous request list for load generation.

    ``method`` picks the workload class for every request ('sa', 'pt',
    'pa') or 'mixed' for a deterministic sa/pt/pa rotation — the
    co-batching stressor: all three classes share slots, device programs
    and the bit-exactness oracle.  PA requests get an ESS-driven width
    schedule (pa_ess_ratio=0.5) so the self-shrink path is exercised.

    ``family`` picks the problem representation: 'continuous' (the six
    registry objectives, float32 coordinate states), 'qap' (built-in QAP
    instances, int32 permutation states; permutations are SA-only, so
    ``method`` must be 'sa'), or 'mixed' — alternating continuous/QAP
    requests co-resident in one slot pool, the cross-representation
    stressor.  QAP entries in a mixed load always run plain SA; the
    continuous entries still follow ``method``.

    ``finish_deadline_factor`` (when set) attaches a completion SLO to
    every request: ``finish_deadline = factor x n_levels`` ticks of
    end-to-end budget, with ``min_levels = max(1, min_levels_frac x
    n_levels)`` as the ladder-truncation floor — factor > 1 leaves slack
    for queueing; the scheduler truncates the ladder (never below the
    floor) when the slack runs out.
    """
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        is_qap = family == "qap" or (family == "mixed" and i % 2 == 1)
        n_slots_i = 1 + int(rng.integers(0, max_slots_per_req))
        if is_qap:
            obj, dim = MIX_QAP_PROBLEMS[(i // 2) % len(MIX_QAP_PROBLEMS)] \
                if family == "mixed" else \
                MIX_QAP_PROBLEMS[i % len(MIX_QAP_PROBLEMS)]
            sched = MIX_QAP_SCHEDULES[i % len(MIX_QAP_SCHEDULES)]
            m, ess, fam = "sa", 0.0, "permutation"
        else:
            obj, dim = MIX_PROBLEMS[i % len(MIX_PROBLEMS)]
            sched = MIX_SCHEDULES[i % len(MIX_SCHEDULES)]
            m = ("sa", "pt", "pa")[i % 3] if method == "mixed" else method
            ess, fam = 0.5 if m == "pa" else 0.0, "continuous"
        req = SARequest(
            req_id=i, objective=obj, dim=dim,
            n_chains=n_slots_i * chains_per_slot,
            seed=seed * 1000 + i, priority=int(rng.integers(0, 3)),
            method=m, pa_ess_ratio=ess, family=fam,
            **sched)
        if finish_deadline_factor is not None:
            req = dataclasses.replace(
                req,
                finish_deadline=finish_deadline_factor * req.n_levels,
                min_levels=max(1, int(min_levels_frac * req.n_levels)))
        reqs.append(req)
    return reqs


def standalone_replay(req: SARequest, res, cfg: EngineConfig):
    """``req`` served alone, replaying what the packed run did to it.

    A degraded admission is bit-exact vs a standalone run at the
    *admitted* chain count (same logical chain indices and RNG); a job
    shrunk mid-flight (drain / proactive degrade) vs a standalone run
    that replays the same width schedule on the level axis, and a
    ladder-truncated job vs one that replays the same truncation
    schedule (cuts move only the ladder's end, so champions are
    prefix-exact).  ``res`` is the packed run's RequestResult.
    """
    solo_req = req if res.admitted_chains >= req.n_chains else \
        dataclasses.replace(req, n_chains=res.admitted_chains)
    sched = [(lvl, to) for lvl, _frm, to in res.shrink_events]
    cuts = [(lvl, to) for lvl, _frm, to in res.truncate_events]
    return run_standalone(solo_req, cfg, shrink_schedule=sched,
                          truncate_schedule=cuts)


def make_arrivals(reqs, kind: str, rate: float, seed: int,
                  burst: int = 4, period: float = 200.0,
                  amplitude: float = 0.8) -> ArrivalProcess:
    if kind == "poisson":
        return ArrivalProcess.poisson(reqs, rate=rate, seed=seed)
    if kind == "bursty":
        return ArrivalProcess.bursty(reqs, rate=rate, burst=burst, seed=seed)
    if kind == "diurnal":
        return ArrivalProcess.diurnal(reqs, rate=rate, period=period,
                                      amplitude=amplitude, seed=seed)
    return ArrivalProcess.batch(reqs)


def _jsonable(obj):
    """Map non-finite floats to None so --json is strict RFC 8259 JSON
    (bare NaN tokens break jq / JSON.parse / Go decoders)."""
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0], epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--requests", type=int, default=32,
                    help="number of requests in the synthetic mix")
    ap.add_argument("--slots", type=int, default=8,
                    help="slot-pool size per shard (concurrent chain blocks)")
    ap.add_argument("--chains-per-slot", type=int, default=32,
                    help="chains per slot == kernel block size")
    ap.add_argument("--devices", type=int, default=1,
                    help="engine shards on the (pool,) device mesh; each "
                         "owns --slots slots (CPU-testable via XLA_FLAGS="
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--macro-k", type=int, default=1,
                    help="temperature levels fused per device dispatch "
                         "(macro-tick size; 1 = classic per-level launch). "
                         "Bit-exact at any value")
    ap.add_argument("--migration-budget", type=int, default=1,
                    help="max cross-shard moves per tick — drain "
                         "evacuation, head defrag and watermark "
                         "rebalancing share it (0 disables all three)")
    ap.add_argument("--drain-at", type=int, default=None,
                    help="tick at which to drain one shard (evacuate and "
                         "retire it mid-stream)")
    ap.add_argument("--drain-shard", type=int, default=None,
                    help="shard index for --drain-at (default: the "
                         "highest-index live shard at that tick)")
    ap.add_argument("--resize", action="append", default=None,
                    metavar="TICK:N",
                    help="resize the fleet to N live shards at TICK "
                         "(repeatable; composes drain/add)")
    ap.add_argument("--high-watermark", type=float, default=1.0,
                    help="shard utilization above which the background "
                         "rebalancer moves work off (1.0 disables)")
    ap.add_argument("--low-watermark", type=float, default=0.0,
                    help="shard utilization below which a shard may "
                         "receive rebalanced work (0.0 disables)")
    ap.add_argument("--proactive-degrade", action="store_true",
                    help="shrink running degrade-class jobs (down to "
                         "min_chains) when the queue head fits nowhere")
    ap.add_argument("--shrink-budget", type=int, default=1,
                    help="max proactive shrinks per tick")
    ap.add_argument("--autoscale", action="store_true",
                    help="attach the closed-loop autoscaler: sample "
                         "backlog/occupancy/deadline headroom every "
                         "--scale-sample-every ticks, resize the fleet "
                         "between --min-shards and --max-shards (scale up "
                         "before predicted completion-SLO misses, drain "
                         "the emptiest shard after --scale-window low-"
                         "utilization samples).  Decisions are tick-"
                         "aligned and deterministic; --check still holds")
    ap.add_argument("--min-shards", type=int, default=1,
                    help="autoscaler fleet floor")
    ap.add_argument("--max-shards", type=int, default=4,
                    help="autoscaler fleet ceiling")
    ap.add_argument("--scale-sample-every", type=int, default=8,
                    help="ticks between autoscaler control samples")
    ap.add_argument("--scale-headroom", type=float, default=1.25,
                    help="demand safety multiplier on scale-up")
    ap.add_argument("--scale-low-util", type=float, default=0.35,
                    help="utilization low watermark for scale-down")
    ap.add_argument("--scale-window", type=int, default=3,
                    help="consecutive low-utilization samples before a "
                         "scale-down (hysteresis)")
    ap.add_argument("--scale-cooldown", type=int, default=32,
                    help="min ticks between fleet-size changes")
    ap.add_argument("--finish-deadline-factor", type=float, default=None,
                    metavar="F",
                    help="attach a completion SLO to every mix request: "
                         "finish_deadline = F x its ladder length "
                         "(min_levels = --min-levels-frac x ladder; the "
                         "scheduler truncates the ladder, never below the "
                         "floor, to meet it)")
    ap.add_argument("--min-levels-frac", type=float, default=0.5,
                    help="ladder-truncation floor as a fraction of each "
                         "request's ladder length")
    ap.add_argument("--method", default="sa",
                    choices=["sa", "pt", "pa", "mixed"],
                    help="workload class for the synthetic mix: plain SA, "
                         "parallel tempering (chains hold rungs of the "
                         "request's temperature ladder with even/odd "
                         "replica swaps each level), population annealing "
                         "(per-level Boltzmann resampling, ESS-driven "
                         "width), or a deterministic sa/pt/pa rotation "
                         "co-batched in the same slot pool")
    ap.add_argument("--family", default="continuous",
                    choices=["continuous", "qap", "mixed"],
                    help="problem family of the synthetic mix: continuous "
                         "(float32 coordinate states, the six registry "
                         "objectives), qap (int32 permutation states over "
                         "the built-in QAP instances; SA-only, so --method "
                         "must stay sa), or mixed — alternating continuous "
                         "and QAP requests co-batched in one slot pool "
                         "(QAP entries always run plain SA)")
    ap.add_argument("--variant", default="delta", choices=["delta", "full"],
                    help="objective evaluation: O(1) delta or O(dim) full "
                         "(continuous family only; QAP always uses the "
                         "delta-evaluated swap sweep)")
    ap.add_argument("--seed", type=int, default=0,
                    help="request-mix generator seed")
    ap.add_argument("--policy", default="priority",
                    choices=["priority", "fifo"],
                    help="admission policy (priority is aged)")
    ap.add_argument("--max-slots-per-req", type=int, default=2,
                    help="largest request footprint in the mix, in slots")
    ap.add_argument("--overload-policy", default="none",
                    choices=["none", "reject", "degrade", "preempt"],
                    help="scheduler-wide overload policy (SLO admission "
                         "control); per-request on_overload overrides it")
    ap.add_argument("--deadline", type=float, default=None,
                    help="queueing-delay SLO in ticks for reject/degrade "
                         "(default: none — requests queue forever)")
    ap.add_argument("--preemption-budget", type=int, default=1,
                    help="max preemptions (swap-outs) per tick")
    ap.add_argument("--arrivals", default="batch",
                    choices=["batch", "poisson", "bursty", "diurnal"],
                    help="closed-loop batch, open-loop Poisson stream, "
                         "bursty overload stream, or a diurnal stream "
                         "(sinusoidal intensity around --rate: the "
                         "autoscaler's day/night envelope)")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="offered load for open-loop arrivals, requests/tick")
    ap.add_argument("--burst", type=int, default=4,
                    help="burst size for --arrivals bursty")
    ap.add_argument("--period", type=float, default=200.0,
                    help="diurnal cycle length in ticks")
    ap.add_argument("--amplitude", type=float, default=0.8,
                    help="diurnal intensity swing in [0, 1] (peak = "
                         "(1+a) x rate, trough = (1-a) x rate)")
    ap.add_argument("--arrival-seed", type=int, default=0,
                    help="seed for the arrival timeline")
    ap.add_argument("--max-ticks", type=int, default=None,
                    help="hard tick budget (default: run to drain)")
    ap.add_argument("--json", dest="as_json", action="store_true",
                    help="emit one JSON document instead of the text report "
                         "(includes a metrics snapshot when telemetry is on)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "run (per-phase tick spans + request lifecycles); "
                         "enables telemetry")
    ap.add_argument("--events", default=None, metavar="OUT.jsonl",
                    help="write the deterministic scheduler-decision log "
                         "(one JSON record per line); enables telemetry")
    ap.add_argument("--metrics", default=None, metavar="OUT.prom",
                    help="write a Prometheus text exposition of the "
                         "metrics registry; enables telemetry")
    ap.add_argument("--check", dest="check", action="store_true",
                    default=True,
                    help="compare every champion vs a standalone run")
    ap.add_argument("--no-check", dest="check", action="store_false")
    args = ap.parse_args(argv)
    if args.family == "qap" and args.method != "sa":
        # Permutations have no temperature-rung replica layout: the
        # request validator rejects pt/pa on the permutation family, so
        # fail fast here with the flag-level explanation.
        ap.error("--family qap serves plain SA only (permutation requests "
                 "have no pt/pa replica layout); drop --method " +
                 args.method)
    if args.overload_policy in ("reject", "degrade") and args.deadline is None:
        # Without a deadline the expiry check can never fire, silently
        # degenerating to --overload-policy none.
        ap.error(f"--overload-policy {args.overload_policy} requires "
                 "--deadline (the queueing-delay SLO it enforces)")
    if args.drain_at is not None and args.devices < 2:
        ap.error("--drain-at needs --devices >= 2 (the survivors absorb "
                 "the drained shard's work)")
    if args.autoscale and not (args.min_shards <= args.devices
                               <= args.max_shards):
        ap.error(f"--autoscale needs --min-shards <= --devices <= "
                 f"--max-shards; got {args.min_shards} <= {args.devices} "
                 f"<= {args.max_shards}")
    resizes = []
    for spec in args.resize or []:
        try:
            t_str, n_str = spec.split(":")
            resizes.append((int(t_str), int(n_str)))
        except ValueError:
            ap.error(f"--resize expects TICK:N, got {spec!r}")
        if resizes[-1][1] < 1:
            ap.error(f"--resize target must be >= 1 shard, got {spec!r}")

    cfg = EngineConfig(
        n_slots=args.slots, chains_per_slot=args.chains_per_slot,
        n_devices=args.devices, variant=args.variant,
        macro_k=args.macro_k,
        migration_budget=args.migration_budget,
        scheduler=SchedulerConfig(policy=args.policy,
                                  overload=args.overload_policy,
                                  default_deadline=args.deadline,
                                  preemption_budget=args.preemption_budget,
                                  high_watermark=args.high_watermark,
                                  low_watermark=args.low_watermark,
                                  proactive_degrade=args.proactive_degrade,
                                  shrink_budget=args.shrink_budget))
    telemetry = None
    if args.trace or args.events or args.metrics:
        from repro.service.telemetry import EventLog, Telemetry
        from repro.service.trace import TraceBuilder
        telemetry = Telemetry(
            trace=TraceBuilder() if args.trace else None,
            events=EventLog() if args.events else None)
    engine = SAServeEngine(cfg, telemetry=telemetry)
    controller = None
    if args.autoscale:
        from repro.service.autoscaler import Autoscaler, AutoscalerConfig
        controller = Autoscaler(AutoscalerConfig(
            min_shards=args.min_shards, max_shards=args.max_shards,
            sample_every=args.scale_sample_every,
            headroom=args.scale_headroom, low_util=args.scale_low_util,
            window=args.scale_window, cooldown=args.scale_cooldown))
        engine.attach_controller(controller)
    # Scripted fleet changes land on the deterministic tick axis.
    for t, n in sorted(resizes):
        engine.schedule_op(t, lambda n=n: engine.resize(n))
    if args.drain_at is not None:
        def _drain():
            target = args.drain_shard if args.drain_shard is not None \
                else max(s.index for s in engine.live_shards)
            engine.drain(target)
        engine.schedule_op(args.drain_at, _drain)
    reqs = make_mix(args.requests, args.chains_per_slot, seed=args.seed,
                    max_slots_per_req=min(args.max_slots_per_req, args.slots),
                    method=args.method, family=args.family,
                    finish_deadline_factor=args.finish_deadline_factor,
                    min_levels_frac=args.min_levels_frac)
    arrivals = make_arrivals(reqs, args.arrivals, args.rate,
                             args.arrival_seed, burst=args.burst,
                             period=args.period, amplitude=args.amplitude)

    results = engine.run_stream(arrivals, max_ticks=args.max_ticks)
    stats = engine.stats()
    lat = latency_summary(results, ticks=engine.tick_count,
                          n_submitted=engine.n_submitted)

    if args.trace:
        with open(args.trace, "w", encoding="utf-8") as fh:
            fh.write(telemetry.trace.dumps())
        if not args.as_json:
            print(f"[serve_sa] trace: {len(telemetry.trace.events)} events "
                  f"-> {args.trace} (open at https://ui.perfetto.dev)")
    if args.events:
        with open(args.events, "w", encoding="utf-8") as fh:
            fh.write(telemetry.events.dumps())
        if not args.as_json:
            print(f"[serve_sa] events: {len(telemetry.events.records)} "
                  f"decision records -> {args.events}")
    if args.metrics:
        with open(args.metrics, "w", encoding="utf-8") as fh:
            fh.write(telemetry.registry.exposition())
        if not args.as_json:
            print(f"[serve_sa] metrics -> {args.metrics}")

    by_id = {r.req_id: r for r in results}
    # Requests with a terminal result, split by status; rejected requests
    # carry no solution to compare.
    served = [req for req in reqs
              if req.req_id in by_id and by_id[req.req_id].completed]
    rejected_ids = sorted(r.req_id for r in results if not r.completed)
    unserved = [req.req_id for req in reqs if req.req_id not in by_id]
    n_exact = 0
    mismatched = {}             # req_id -> report line
    if args.check:
        for req in served:
            res = by_id[req.req_id]
            solo = standalone_replay(req, res, cfg)
            if res.f_best == solo.f_best:
                n_exact += 1
            else:
                mismatched[req.req_id] = (
                    f"req{req.req_id}: packed {res.f_best:+.5f}"
                    f" != standalone {solo.f_best:+.5f}")
    # The check must not pass vacuously: a truncated run (--max-ticks) that
    # served nothing is a coverage failure, not a success.  Rejection is a
    # terminal status, not a coverage hole.
    check_failed = args.check and (n_exact != len(served) or unserved)

    if args.as_json:
        doc = {
            "config": {
                "requests": args.requests, "slots": args.slots,
                "chains_per_slot": args.chains_per_slot,
                "devices": args.devices, "macro_k": args.macro_k,
                "migration_budget": args.migration_budget,
                "drain_at": args.drain_at, "drain_shard": args.drain_shard,
                "resize": sorted(resizes),
                "high_watermark": args.high_watermark,
                "low_watermark": args.low_watermark,
                "proactive_degrade": args.proactive_degrade,
                "shrink_budget": args.shrink_budget,
                "method": args.method, "family": args.family,
                "variant": args.variant, "policy": args.policy,
                "overload_policy": args.overload_policy,
                "deadline": args.deadline,
                "preemption_budget": args.preemption_budget,
                "seed": args.seed, "arrivals": args.arrivals,
                "rate": args.rate, "burst": args.burst,
                "period": args.period, "amplitude": args.amplitude,
                "arrival_seed": args.arrival_seed,
                "autoscale": args.autoscale,
                "min_shards": args.min_shards,
                "max_shards": args.max_shards,
                "finish_deadline_factor": args.finish_deadline_factor,
                "min_levels_frac": args.min_levels_frac,
            },
            "stats": stats,
            "latency": lat,
            "results": [r.to_dict()
                        for r in sorted(results, key=lambda r: r.req_id)],
        }
        if controller is not None:
            doc["autoscaler"] = {
                "samples": controller.samples,
                "decisions": [list(d) for d in controller.decisions],
            }
        if telemetry is not None:
            doc["metrics"] = telemetry.registry.snapshot()
        if args.check:
            doc["check"] = {"bit_exact": n_exact, "served": len(served),
                            "rejected_req_ids": rejected_ids,
                            "unserved_req_ids": unserved,
                            "mismatches": sorted(mismatched.values())}
        print(json.dumps(_jsonable(doc), indent=2, sort_keys=True,
                         allow_nan=False))
    else:
        print(f"[serve_sa] {stats['completed']}/{args.requests} requests in "
              f"{stats['ticks']} ticks, {stats['wall_s']:.2f}s | "
              f"{stats['requests_per_s']:.2f} req/s, "
              f"{stats['sweeps_per_s']:.1f} sweeps/s, "
              f"{stats['chain_steps_per_s']:.3g} chain-steps/s | "
              f"occupancy {stats['occupancy']:.1%}")
        if args.devices > 1 or stats["shards_retired"]:
            shard_util = " ".join(f"{u:.0%}" for u in
                                  stats["shard_occupancy"])
            print(f"[serve_sa] {stats['devices']} shards x {args.slots} "
                  f"slots (started with {args.devices}): per-shard "
                  f"utilization [{shard_util}], "
                  f"{stats['migrations']} migrations")
        if stats["shards_retired"] or stats["draining"] or stats["shrinks"]:
            retired = ", ".join(f"shard {i} at tick {t}"
                                for i, t in engine.retired_shards)
            print(f"[serve_sa] elastic fleet: {stats['shards_retired']} "
                  f"retired ({retired or 'none'}), {stats['draining']} "
                  f"still draining, {stats['shrinks']} proactive shrinks")
        if controller is not None:
            moves = " ".join(f"t{t}:{kind[0]}{a}->{b}"
                             for t, kind, a, b in controller.decisions)
            print(f"[serve_sa] autoscaler: {controller.samples} samples, "
                  f"{len(controller.decisions)} fleet changes "
                  f"[{moves or 'none'}]")
        if stats["truncations"]:
            print(f"[serve_sa] completion SLO: {stats['truncations']} "
                  f"ladder truncations across "
                  f"{sum(1 for r in results if r.truncated)} requests")
        if lat["incomplete"]:
            print(f"[serve_sa] {lat['incomplete']} requests still in flight "
                  f"or queued at the --max-ticks horizon (not rejected)")
        if args.arrivals != "batch":
            print(f"[serve_sa] open loop @ {args.rate} req/tick: "
                  f"queue delay p50/p99 = {lat['queue_delay_p50']:.1f}/"
                  f"{lat['queue_delay_p99']:.1f} ticks, "
                  f"ttft p50/p99 = {lat['ttft_p50']:.1f}/"
                  f"{lat['ttft_p99']:.1f} ticks, "
                  f"goodput {lat['goodput_req_per_tick']:.3f} req/tick")
        if args.overload_policy != "none" or stats["rejected"] \
                or stats["preemptions"]:
            print(f"[serve_sa] overload policy '{args.overload_policy}': "
                  f"{stats['rejected']} rejected, "
                  f"{stats['preemptions']} preemptions")
        for req in served:
            res = by_id[req.req_id]
            line = (f"  req{req.req_id:>3} {req.objective:<10} d={req.dim:<3} "
                    f"f_best={res.f_best:+.5f} levels={res.levels_run} "
                    f"wait={res.queue_delay_ticks:.1f}t "
                    f"[{res.finish_reason}]")
            if res.n_preemptions:
                line += f" preempted x{res.n_preemptions}"
            if res.n_migrations:
                line += f" migrated x{res.n_migrations}"
            if res.n_shrinks:
                line += (f" shrunk x{res.n_shrinks} "
                         f"({res.admitted_chains}->{res.granted_chains} "
                         "chains)")
            if res.truncated:
                line += (f" truncated x{res.n_truncations} "
                         f"({res.truncate_events[0][1]}->"
                         f"{res.truncate_events[-1][2]} levels)")
            elif res.degraded:
                line += (f" degraded {res.granted_chains}/"
                         f"{res.requested_chains} chains")
            if args.check:
                line += ("  != standalone" if req.req_id in mismatched
                         else "  == standalone")
            print(line)
        for rid in rejected_ids:
            res = by_id[rid]
            print(f"  req{rid:>3} {res.objective:<10} d={res.dim:<3} "
                  f"REJECTED at tick {res.finish_tick} "
                  f"(queued {res.finish_tick - res.submit_tick}t)")
        if args.check:
            tail = f" ({len(unserved)} never served)" if unserved else ""
            print(f"[serve_sa] {n_exact}/{len(served)} champions bit-exact "
                  f"vs standalone{tail}")
            for rid in sorted(mismatched):
                print("  " + mismatched[rid])

    if check_failed:
        raise SystemExit(1)
    return results


if __name__ == "__main__":
    use_compile_cache()
    main()
