"""Multi-tenant SA serving engine: continuous batching for annealing jobs.

The paper's synchronous SA (V2) is a single-job batch program.  This
subsystem turns it into a *serving* system in the vLLM/LightLLM mold: a
fixed pool of chain-block slots, an admission scheduler that packs a queue
of heterogeneous optimization requests into free slots, one engine tick =
one temperature level for every active slot, and immediate slot refill when
a request's ladder (or budget, or accuracy target) completes.

Layers
------
``request.py``   : :class:`SARequest` / :class:`RequestResult` schema,
                   SLO fields (deadline, min-chains, overload class),
                   lifecycle timestamps + derived latencies.
``slots.py``     : the slot pool — per-slot chain state + ownership —
                   and :class:`SwappedJob` preemption checkpoints.
``sharding.py``  : the sharded pool — one :class:`EngineShard` (private
                   slot pool + rid table) per device on the 1-D
                   ``(pool,)`` mesh.
``scheduler.py`` : priority-with-aging admission, bounded backfill,
                   the reject/degrade/preempt overload policies, and the
                   placement layer (home-shard choice, Russkov-style
                   cross-shard migration planning, drain evacuation,
                   watermark rebalancing, proactive-degrade shrinks).
``arrivals.py``  : open-loop arrival processes (seeded Poisson / bursty /
                   diurnal / trace / batch) + latency percentile summaries.
``autoscaler.py``: closed-loop fleet controller — samples backlog /
                   occupancy / completion headroom on a tick cadence,
                   grows ahead of predicted deadline misses, drains after
                   sustained idleness (hysteresis + cooldown).
``engine.py``    : the continuous-batching tick loop; per-slot objective id
                   (runtime — no recompile per objective), temperature,
                   seed and step cursor threaded to the Pallas kernel,
                   champion exchange masked per request (tenant isolation).
``serve_sa.py``  : CLI driver + synthetic heterogeneous load, closed- or
                   open-loop (``--arrivals poisson --rate ...``).
``telemetry.py`` : opt-in observability bundle — metrics registry
                   (counters/gauges/streaming histograms, Prometheus
                   text + JSON export), per-phase tick timers, the
                   deterministic decision event log, and the jax
                   compile-event counter.  Off by default: zero overhead,
                   bit-exact when on (docs/observability.md).
``trace.py``     : Chrome/Perfetto ``trace_event`` builder + checked-in
                   schema validation (``serve_sa --trace out.json``).

Usage::

    from repro.service import EngineConfig, SARequest, SAServeEngine

    engine = SAServeEngine(EngineConfig(n_slots=8, chains_per_slot=32))
    engine.submit(SARequest(req_id=0, objective="rastrigin", dim=8,
                            n_chains=64, T0=100.0, T_min=0.5, rho=0.9, N=40))
    engine.submit(SARequest(req_id=1, objective="ackley", dim=16,
                            n_chains=32, T0=50.0, T_min=0.2, rho=0.95, N=25))
    results = engine.run()          # both jobs co-annealed on one program
    print(engine.stats())           # req/s, sweeps/s, slot occupancy

Or from the shell::

    PYTHONPATH=src python -m repro.service.serve_sa --requests 32 --slots 8
"""
from repro.service.arrivals import ArrivalProcess, latency_summary
from repro.service.autoscaler import Autoscaler, AutoscalerConfig
from repro.service.engine import EngineConfig, SAServeEngine, run_standalone
from repro.service.request import (OVERLOAD_POLICIES, RequestResult,
                                   SARequest, SERVABLE, TERMINAL_REASONS)
from repro.service.scheduler import (AdmissionPlan, AdmissionScheduler,
                                     QueueEntry, SchedulerConfig, ShardView)
from repro.service.sharding import EngineShard, slot_pool_devices
from repro.service.slots import ActiveJob, SlotPool, SwappedJob
from repro.service.telemetry import (EventLog, MetricsRegistry, PhaseTimer,
                                     SubPhaseTimer, Telemetry, TICK_PHASES,
                                     TICK_SUBPHASES, compile_events)
from repro.service.trace import TraceBuilder, validate_trace

__all__ = [
    "EngineConfig", "SAServeEngine", "run_standalone",
    "SARequest", "RequestResult", "SERVABLE", "OVERLOAD_POLICIES",
    "TERMINAL_REASONS",
    "AdmissionScheduler", "AdmissionPlan", "QueueEntry", "SchedulerConfig",
    "ShardView",
    "SlotPool", "ActiveJob", "SwappedJob",
    "EngineShard", "slot_pool_devices",
    "ArrivalProcess", "latency_summary",
    "Autoscaler", "AutoscalerConfig",
    "Telemetry", "MetricsRegistry", "PhaseTimer", "SubPhaseTimer",
    "EventLog", "TICK_PHASES", "TICK_SUBPHASES", "compile_events",
    "TraceBuilder", "validate_trace",
]
