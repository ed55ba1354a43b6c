"""Engine telemetry: metrics registry, phase timers, structured events.

The serving stack's counters (``engine.stats()``) answer *what happened*
— requests, sweeps, migrations — but not *where a tick's wall time goes*.
``BENCH_serve_scale.json`` shows why that matters: 4 shards deliver ~5x
goodput **per tick** yet worse wall-clock than 2 shards, because the
Python tick loop and per-tick launch/sync overhead are invisible to every
per-tick counter.  This module is the host/device accounting layer that
localizes the cost (the discipline of Barash et al.'s population-annealing
GPU accounting, applied to a serving loop):

* :class:`MetricsRegistry` — typed counters / gauges / histograms with
  label support, streaming p50/p90/p99 (exponential-bucket histograms:
  O(1) memory, deterministic), a Prometheus-style text exposition and a
  JSON snapshot.  Per-shard series are labelled by stable shard index, so
  a retired shard's counters survive drain/resize.
* :class:`PhaseTimer` / :class:`NullPhaseTimer` — monotonic span
  accumulation for the engine tick's phases (``schedule / admit /
  dispatch / device_wait / materialize / retire``), per shard and
  aggregate, each also opened as a ``jax.profiler.TraceAnnotation``
  (``sa.<phase>``) so the profiler's device trace sees it on its own
  clock.  :class:`SubPhaseTimer` does the same one level down
  (``sa.<phase>.<sub>``, :data:`TICK_SUBPHASES`).  The null variant is a
  reusable no-op context manager shared by both: telemetry off means
  **zero span objects allocated** per tick (tests assert this via
  :attr:`PhaseTimer.spans_entered` and :attr:`SubPhaseTimer.spans_entered`).
* :class:`EventLog` — seeded-deterministic one-line-JSON records of every
  scheduler/engine *decision* (admit, resume, preempt, migrate, shrink,
  reject, retire, drain, shard lifecycle).  Records carry tick-clock
  fields only, so the same seeded stream replays to byte-identical logs —
  a scheduler-decision regression oracle (``serve_sa --events``).
* :func:`compile_events` — a process-wide ``jax`` compile-hook counter
  (``jax.monitoring`` backend-compile events), the witness that telemetry
  adds **zero compiled programs**.

Everything here is host-side observation: enabling telemetry never
touches a device buffer, an RNG stream, or an admission decision, so
trajectories stay bit-exact (``serve_sa --check --trace ...`` proves it).
The one *timing* perturbation is deliberate: with phase timing enabled
the engine fences each shard's launches with ``jax.block_until_ready``
so host-side launch cost separates from device compute — a measurement
choice, not a semantic one (docs/observability.md).
"""
from __future__ import annotations

import json
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

#: The engine tick's phase taxonomy, in execution order (docs/observability.md):
#:   schedule     — scheduler planning (placement, migration, shrink, admit plans)
#:   admit        — executing the plans (checkpoint/restore, slot assignment)
#:   dispatch     — host-side packing + async device-program launches
#:   device_wait  — block_until_ready fence: device compute the host waits on
#:   materialize  — device->host reads of the champions, folded on host
#:   retire       — finish checks, result records, slot release
TICK_PHASES = ("schedule", "admit", "dispatch", "device_wait",
               "materialize", "retire")

#: Spans one level under a phase, named ``<phase>.<sub>``
#: (docs/observability.md):
#:   admit.place          — one queued job put on the shard the scheduler
#:                          chose: slots, then init_state or restore
#:   admit.init_state     — a new request's initial chain states (sample_x0)
#:   admit.restore        — checkpoint / restore copies of resident blocks
#:   dispatch.pack        — the packed state and per-block controls on host
#:   dispatch.h2d         — the device_put calls
#:   dispatch.launch      — the jitted group program's call (the enqueue;
#:                          a compile lands here)
#:   materialize.d2h      — np.asarray reads of the program's outputs
#:   materialize.fold     — champion fold, history and finish checks
TICK_SUBPHASES = ("admit.place", "admit.init_state", "admit.restore",
                  "dispatch.pack", "dispatch.h2d", "dispatch.launch",
                  "materialize.d2h", "materialize.fold")

#: Profiler annotation names, built once: ``sa.<phase>`` and
#: ``sa.<phase>.<sub>``.
_ANNOTATION = {name: "sa." + name for name in TICK_PHASES + TICK_SUBPHASES}


# --------------------------------------------------------------------- metrics
class Counter:
    """Monotonic counter, optionally labelled."""

    kind = "counter"

    def __init__(self, name: str, help: str, labels: Sequence[str] = ()):
        self.name = name
        self.help = help
        self.labels = tuple(labels)
        self.series: Dict[Tuple, float] = {}

    def _key(self, labelvalues: Tuple) -> Tuple:
        if len(labelvalues) != len(self.labels):
            raise ValueError(
                f"{self.name} expects labels {self.labels}, "
                f"got {labelvalues}")
        return labelvalues

    def inc(self, value: float = 1.0, *labelvalues) -> None:
        if value < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        key = self._key(labelvalues)
        self.series[key] = self.series.get(key, 0.0) + value

    def value(self, *labelvalues) -> float:
        return self.series.get(self._key(labelvalues), 0.0)

    def snapshot(self) -> dict:
        return {self._fmt(k): v for k, v in sorted(self.series.items())}

    def _fmt(self, key: Tuple) -> str:
        if not self.labels:
            return ""
        return ",".join(f"{n}={v}" for n, v in zip(self.labels, key))

    def expose(self) -> List[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} {self.kind}"]
        for key, v in sorted(self.series.items()):
            lines.append(f"{self.name}{_promlabels(self.labels, key)} {_num(v)}")
        return lines


class Gauge(Counter):
    """Point-in-time value, optionally labelled."""

    kind = "gauge"

    def set(self, value: float, *labelvalues) -> None:
        self.series[self._key(labelvalues)] = float(value)

    def inc(self, value: float = 1.0, *labelvalues) -> None:
        key = self._key(labelvalues)
        self.series[key] = self.series.get(key, 0.0) + value


class Histogram:
    """Streaming distribution: exponential buckets + count/sum/min/max.

    Quantiles are estimated by log-linear interpolation inside the bucket
    the cumulative count lands in — O(n_buckets) memory regardless of how
    many observations stream through, and fully deterministic (no
    reservoir sampling).  Bucket error is bounded by ``growth`` (default
    1.25: <= 12% relative error on any quantile), which is ample for
    localizing where milliseconds go.
    """

    kind = "histogram"

    def __init__(self, name: str, help: str, labels: Sequence[str] = (),
                 lo: float = 1e-6, hi: float = 1e3, growth: float = 1.25):
        if not (0 < lo < hi and growth > 1):
            raise ValueError("need 0 < lo < hi and growth > 1")
        self.name = name
        self.help = help
        self.labels = tuple(labels)
        self.lo, self.growth = lo, growth
        n = int(math.ceil(math.log(hi / lo) / math.log(growth)))
        #: bucket b spans [lo*growth^(b-1), lo*growth^b); bucket 0 is
        #: (-inf, lo); bucket n+1 is the +inf overflow.
        self.n_buckets = n + 2
        self.series: Dict[Tuple, dict] = {}

    def _state(self, labelvalues: Tuple) -> dict:
        if len(labelvalues) != len(self.labels):
            raise ValueError(
                f"{self.name} expects labels {self.labels}, "
                f"got {labelvalues}")
        st = self.series.get(labelvalues)
        if st is None:
            st = self.series[labelvalues] = {
                "buckets": [0] * self.n_buckets, "count": 0, "sum": 0.0,
                "min": float("inf"), "max": float("-inf")}
        return st

    def _bucket(self, v: float) -> int:
        if v < self.lo:
            return 0
        b = 1 + int(math.log(v / self.lo) / math.log(self.growth))
        return min(b, self.n_buckets - 1)

    def _edge(self, b: int) -> float:
        """Upper edge of bucket ``b``."""
        if b == 0:
            return self.lo
        return self.lo * self.growth ** b

    def observe(self, value: float, *labelvalues) -> None:
        st = self._state(labelvalues)
        st["buckets"][self._bucket(value)] += 1
        st["count"] += 1
        st["sum"] += value
        st["min"] = min(st["min"], value)
        st["max"] = max(st["max"], value)

    def quantile(self, q: float, *labelvalues) -> float:
        """Estimated q-quantile (q in [0, 1]); nan with no observations."""
        st = self.series.get(tuple(labelvalues))
        if st is None or not st["count"]:
            return float("nan")
        rank = q * st["count"]
        seen = 0
        for b, n in enumerate(st["buckets"]):
            if n and seen + n >= rank:
                lo_edge = self._edge(b - 1) if b else st["min"]
                hi_edge = self._edge(b)
                frac = (rank - seen) / n
                est = lo_edge + (hi_edge - lo_edge) * frac
                return float(min(max(est, st["min"]), st["max"]))
            seen += n
        return float(st["max"])

    def summary(self, *labelvalues) -> dict:
        st = self.series.get(tuple(labelvalues))
        if st is None or not st["count"]:
            return {"count": 0, "sum": 0.0}
        return {
            "count": st["count"], "sum": st["sum"],
            "min": st["min"], "max": st["max"],
            "mean": st["sum"] / st["count"],
            "p50": self.quantile(0.50, *labelvalues),
            "p90": self.quantile(0.90, *labelvalues),
            "p99": self.quantile(0.99, *labelvalues),
        }

    def snapshot(self) -> dict:
        out = {}
        for key in sorted(self.series):
            label = ",".join(f"{n}={v}" for n, v in zip(self.labels, key))
            out[label] = self.summary(*key)
        return out

    def expose(self) -> List[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} summary"]
        for key, st in sorted(self.series.items()):
            for q in (0.5, 0.9, 0.99):
                qlabels = _promlabels(
                    self.labels + ("quantile",), key + (f"{q:g}",))
                lines.append(
                    f"{self.name}{qlabels} {_num(self.quantile(q, *key))}")
            base = _promlabels(self.labels, key)
            lines.append(f"{self.name}_sum{base} {_num(st['sum'])}")
            lines.append(f"{self.name}_count{base} {st['count']}")
        return lines


def _promlabels(names: Sequence[str], values: Tuple) -> str:
    if not names:
        return ""
    body = ",".join(f'{n}="{v}"' for n, v in zip(names, values))
    return "{" + body + "}"


def _num(v: float) -> str:
    if isinstance(v, float) and math.isnan(v):
        return "NaN"
    return f"{v:.9g}" if isinstance(v, float) else str(v)


class MetricsRegistry:
    """Named metric store with Prometheus text + JSON export.

    Metric creation is idempotent (``counter(name)`` returns the existing
    series on a repeat call) so engine layers can declare what they need
    without coordinating.  Per-shard series carry the stable shard index
    as a label — shard retirement never deletes a series, which is how
    metrics survive drain/resize (tests assert it).
    """

    def __init__(self):
        self._metrics: Dict[str, object] = {}

    def _get(self, cls, name: str, help: str, labels: Sequence[str], **kw):
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = cls(name, help, labels, **kw)
        elif not isinstance(m, cls) or m.labels != tuple(labels):
            raise ValueError(f"metric {name} re-registered with a different "
                             "type or label set")
        return m

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> Counter:
        return self._get(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> Gauge:
        return self._get(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = (), **kw) -> Histogram:
        return self._get(Histogram, name, help, labels, **kw)

    def __contains__(self, name: str) -> bool:
        return name in self._metrics

    def __getitem__(self, name: str):
        return self._metrics[name]

    def names(self) -> List[str]:
        return sorted(self._metrics)

    def snapshot(self) -> dict:
        """JSON-ready dump: name -> {kind, series} (``serve_sa --json``)."""
        return {name: {"kind": m.kind, "help": m.help,
                       "series": m.snapshot()}
                for name, m in sorted(self._metrics.items())}

    def exposition(self) -> str:
        """Prometheus text format (one scrape page)."""
        lines: List[str] = []
        for name in sorted(self._metrics):
            lines.extend(self._metrics[name].expose())
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------- phase timers
class PhaseTimer:
    """Accumulates monotonic spans per (phase, shard) within one tick.

    Used as a reusable context manager::

        with timer("dispatch", shard=3):
            ...

    Spans never nest (the tick's phases are sequential), so one instance
    re-enters itself — no timer object allocated per span.  ``drain()``
    returns and resets the accumulated (aggregate, per-shard, raw span,
    host-CPU) state; :meth:`Telemetry.end_tick` folds it into histograms
    / trace events at tick end.

    Each span records **two** clocks: monotonic wall time and the host
    thread's CPU time (``time.thread_time``).  On a host core dedicated to
    the engine loop the two agree; when the host shares cores with device
    compute threads (CPU backend, oversubscribed CI runners) wall spans
    absorb whatever work the OS timesliced in, while thread-CPU counts
    only cycles the engine loop itself burned — the durable measure of
    host-side cost per phase.

    Inside the timed span it also opens a profiler annotation
    ``sa.<phase>`` (the profiler's clock, beside the device ops), with the
    argument ``shard=<index>`` when the span is one shard's (``dispatch``,
    ``device_wait``, ``materialize``), and counts the backend compiles
    that land in the span (:func:`compile_events`, into ``compiles``).
    """

    #: Class-wide count of spans ever entered — the zero-overhead witness:
    #: with telemetry disabled this must not move (tests assert it).
    spans_entered = 0

    __slots__ = ("_clock", "acc", "shard_acc", "raw", "cpu_acc", "keep_raw",
                 "compiles", "_phase", "_shard", "_t0", "_c0", "_n0", "_ann")

    def __init__(self, clock, keep_raw: bool = False):
        self._clock = clock         # monotonic epoch-relative seconds
        self.keep_raw = keep_raw    # record (phase, shard, t0, t1) spans
        self.acc: Dict[str, float] = {}
        self.shard_acc: Dict[Tuple[int, str], float] = {}
        self.raw: List[Tuple[str, Optional[int], float, float]] = []
        self.cpu_acc: Dict[str, float] = {}
        self.compiles: Dict[str, int] = {}

    def __call__(self, phase: str, shard: Optional[int] = None):
        self._phase, self._shard = phase, shard
        return self

    def __enter__(self):
        PhaseTimer.spans_entered += 1
        self._t0 = self._clock()
        self._c0 = time.thread_time()
        self._n0 = compile_events()
        name = _ANNOTATION[self._phase]
        self._ann = (TraceAnnotation(name) if self._shard is None else
                     TraceAnnotation(name, shard=self._shard))
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(None, None, None)
        n = compile_events() - self._n0
        dc = time.thread_time() - self._c0
        t1 = self._clock()
        dt = t1 - self._t0
        self.acc[self._phase] = self.acc.get(self._phase, 0.0) + dt
        self.cpu_acc[self._phase] = self.cpu_acc.get(self._phase, 0.0) + dc
        if n:
            self.compiles[self._phase] = self.compiles.get(self._phase, 0) + n
        if self._shard is not None:
            key = (self._shard, self._phase)
            self.shard_acc[key] = self.shard_acc.get(key, 0.0) + dt
        if self.keep_raw:
            self.raw.append((self._phase, self._shard, self._t0, t1))
        return False

    def drain(self):
        acc, shard_acc, raw, cpu = (self.acc, self.shard_acc, self.raw,
                                    self.cpu_acc)
        self.acc, self.shard_acc, self.raw, self.cpu_acc = {}, {}, [], {}
        return acc, shard_acc, raw, cpu


class SubPhaseTimer:
    """Spans one level under a phase (:data:`TICK_SUBPHASES`).

    The same reusable context manager as :class:`PhaseTimer`, but
    re-entrant: a sub-span may open while another is open (a population-
    annealing shrink's ``admit.restore`` runs inside ``materialize.fold``),
    so the open spans sit on a stack.  Each span's wall seconds go to
    ``acc`` (never to the phase sums), its profiler annotation is
    ``sa.<phase>.<sub>``, and with ``keep_raw`` its ``(name, shard, t0,
    t1)`` goes to ``raw`` for the trace document.
    """

    #: Class-wide count of sub-spans ever entered (the same zero-overhead
    #: witness as :attr:`PhaseTimer.spans_entered`).
    spans_entered = 0

    __slots__ = ("_clock", "keep_raw", "acc", "raw", "_name", "_shard",
                 "_stack")

    def __init__(self, clock, keep_raw: bool = False):
        self._clock = clock
        self.keep_raw = keep_raw
        self.acc: Dict[str, float] = {}
        self.raw: List[Tuple[str, Optional[int], float, float]] = []
        self._stack: List[tuple] = []   # (name, shard, t0, annotation)

    def __call__(self, name: str, shard: Optional[int] = None):
        self._name, self._shard = name, shard
        return self

    def __enter__(self):
        SubPhaseTimer.spans_entered += 1
        self._stack.append((self._name, self._shard, self._clock(),
                            TraceAnnotation(_ANNOTATION[self._name])))
        return self

    def __exit__(self, *exc):
        name, shard, t0, ann = self._stack.pop()
        ann.__exit__(None, None, None)
        t1 = self._clock()
        self.acc[name] = self.acc.get(name, 0.0) + (t1 - t0)
        if self.keep_raw:
            self.raw.append((name, shard, t0, t1))
        return False

    def drain(self):
        acc, raw = self.acc, self.raw
        self.acc, self.raw = {}, []
        return acc, raw


class NullPhaseTimer:
    """No-op spans: one shared instance, no state, no allocation.  The
    null twin of both :class:`PhaseTimer` and :class:`SubPhaseTimer`."""

    __slots__ = ()

    def __call__(self, phase, shard=None):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def drain(self):
        return {}, {}, [], {}


NULL_PHASE_TIMER = NullPhaseTimer()


# ------------------------------------------------------------------ event log
class EventLog:
    """Deterministic one-line-JSON decision log.

    Every record is ``{"tick": int, "event": str, ...}`` with tick-clock
    fields only — no wall time, no object ids — so the same seeded stream
    produces byte-identical logs run-to-run (the scheduler-decision
    regression oracle).  Keys are emitted sorted; one record per line
    (JSONL, ``serve_sa --events out.jsonl``).
    """

    def __init__(self):
        self.records: List[dict] = []

    def emit(self, tick: int, event: str, **fields) -> None:
        rec = {"tick": int(tick), "event": event}
        rec.update(fields)
        self.records.append(rec)

    def lines(self) -> List[str]:
        return [json.dumps(r, sort_keys=True, separators=(",", ":"))
                for r in self.records]

    def dumps(self) -> str:
        return "\n".join(self.lines()) + ("\n" if self.records else "")

    @staticmethod
    def loads(text: str) -> List[dict]:
        """Parse a JSONL log back into records (the replay side)."""
        return [json.loads(line) for line in text.splitlines() if line]


# ---------------------------------------------------------- jax compile hook
_COMPILE_EVENTS = {"count": 0, "seconds": 0.0}
_HOOK_INSTALLED = False

#: jax.monitoring duration-event key emitted once per backend (XLA)
#: compilation — the ground truth for "telemetry adds zero compiled
#: programs".  Internal jits count too, which is fine for a delta test.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _install_compile_hook() -> None:
    global _HOOK_INSTALLED
    if _HOOK_INSTALLED:
        return
    import jax.monitoring as _mon

    def _listener(name, secs, **kw):
        if name == _BACKEND_COMPILE_EVENT:
            _COMPILE_EVENTS["count"] += 1
            _COMPILE_EVENTS["seconds"] += secs

    _mon.register_event_duration_secs_listener(_listener)
    _HOOK_INSTALLED = True


def compile_events() -> int:
    """Process-wide count of XLA backend compilations observed so far.

    Installs the (idempotent, listener-only) ``jax.monitoring`` hook on
    first call.  Compare before/after a run to prove a feature added no
    compiled programs — telemetry's own acceptance test does exactly that.
    """
    _install_compile_hook()
    return _COMPILE_EVENTS["count"]


def compile_seconds() -> float:
    """Process-wide wall seconds spent in XLA backend compilations so far
    (the same hook as :func:`compile_events`)."""
    _install_compile_hook()
    return _COMPILE_EVENTS["seconds"]


# ------------------------------------------------------------------- facade
class Telemetry:
    """The engine's observability bundle: metrics + spans + trace + events.

    Construct one and hand it to :class:`~repro.service.engine.SAServeEngine`;
    the default is the module-level :data:`NULL` singleton, whose every
    hook is a no-op — the disabled path allocates no span objects and
    registers no metrics (zero overhead, bit-for-bit identical behavior).

    ``trace`` is an optional
    :class:`~repro.service.trace.TraceBuilder`; when set, per-phase tick
    spans and request lifecycle events are recorded for Perfetto.
    ``events`` is an optional :class:`EventLog` for the deterministic
    decision log.  Phase *fencing* (the ``device_wait`` separation via
    ``block_until_ready``) is implied by ``enabled``.
    """

    enabled = True

    def __init__(self, trace=None, events: Optional[EventLog] = None):
        self.registry = MetricsRegistry()
        self.trace = trace
        self.events = events
        self.compile_events_start = compile_events()
        # Declared up front so an exposition before the first tick is
        # well-formed, and so layer code can .inc() without re-declaring.
        r = self.registry
        self.m_tick_phase = r.histogram(
            "sa_tick_phase_seconds",
            "Wall seconds per engine tick phase", ("phase",))
        self.m_shard_phase = r.counter(
            "sa_shard_phase_seconds_total",
            "Cumulative wall seconds per shard per tick phase",
            ("shard", "phase"))
        self.m_phase_cpu = r.counter(
            "sa_tick_phase_cpu_seconds_total",
            "Cumulative host-thread CPU seconds per tick phase "
            "(thread_time: excludes time the OS gave to other threads)",
            ("phase",))
        self.m_tick = r.histogram(
            "sa_tick_seconds", "Wall seconds per engine tick")
        self.m_ticks = r.counter("sa_ticks_total", "Engine ticks executed")
        self.m_queue_depth = r.gauge(
            "sa_queue_depth", "Requests waiting in the admission queue")
        self.m_active = r.gauge(
            "sa_active_requests", "Requests resident in slots")
        self.m_slot_occupancy = r.gauge(
            "sa_slot_occupancy", "Fraction of fleet slots held by tenants")
        self.m_shard_slots_used = r.gauge(
            "sa_shard_slots_used", "Slots held per shard", ("shard",))
        self.m_decisions = r.counter(
            "sa_scheduler_decisions_total",
            "Scheduler/engine lifecycle decisions", ("decision",))
        self.m_tenant_slot_ticks = r.counter(
            "sa_tenant_slot_ticks_total",
            "Slot-ticks consumed per tenant (the fairness currency)",
            ("req_id",))
        self.m_compile_events = r.counter(
            "sa_jax_compile_events_total",
            "XLA backend compilations observed since engine construction")
        self.m_launches = r.counter(
            "sa_group_launches_total", "Device-program launches")
        self.m_plans = r.counter(
            "sa_scheduler_plans_total",
            "Actions planned per scheduler planner", ("plan",))
        self.m_subphase = r.counter(
            "sa_tick_subphase_seconds_total",
            "Cumulative wall seconds per span under a tick phase "
            "(<phase>.<sub>; not part of sa_tick_phase_seconds)", ("span",))
        self.m_compiles = r.counter(
            "sa_compiles_total",
            "XLA backend compilations seen inside each tick phase",
            ("phase",))
        self.m_state_bytes = r.counter(
            "sa_state_bytes_total",
            "Chain-state bytes moved between host and device by launches "
            "that pack the state anew on host", ("direction",))
        self.m_block_steps = r.counter(
            "sa_block_steps_total",
            "Block-steps launched (one slot block, one Metropolis step): "
            "live, padded to a power of two, or dead in a macro-tick",
            ("kind",))
        self.m_state_buffer = r.counter(
            "sa_state_buffer_total",
            "Launches whose device state buffer was reused (hit) or "
            "packed anew on host (repack)", ("result",))
        self.m_placements = r.counter(
            "sa_placements_total",
            "Queued jobs placed on a shard (new or swapped back in)",
            ("shard",))
        self.m_shard_idle_ticks = r.counter(
            "sa_shard_idle_ticks_total",
            "Ticks in which a live shard held no job and launched nothing",
            ("shard",))

    # -- hooks the engine calls (every one a no-op on NullTelemetry) --
    def make_phase_timer(self, clock) -> PhaseTimer:
        return PhaseTimer(clock, keep_raw=self.trace is not None)

    def make_subphase_timer(self, clock) -> SubPhaseTimer:
        return SubPhaseTimer(clock, keep_raw=self.trace is not None)

    def decision(self, tick: int, kind: str, **fields) -> None:
        """Record one scheduler/engine decision: counter + event record.
        (Trace instants are emitted separately by the engine, on the
        request's own async track.)"""
        self.m_decisions.inc(1, kind)
        if self.events is not None:
            self.events.emit(tick, kind, **fields)

    def plan(self, kind: str, n_actions: int) -> None:
        """Scheduler hook: ``n_actions`` planned by planner ``kind``."""
        self.m_plans.inc(n_actions, kind)

    def end_tick(self, tick: int, phases: PhaseTimer,
                 subphases: SubPhaseTimer, shards, queue_depth: int,
                 n_active: int, levels: int = 1) -> None:
        """Drain one tick's spans from the engine's two timers and fold
        them + fleet state into the registry and trace.

        ``levels`` is how many ladder levels the engine tick advanced (the
        macro-tick factor K when work ran fused, 1 otherwise):
        ``sa_ticks_total`` counts ladder levels, keeping it equal to the
        engine's ``tick_count`` clock at any K.  Phase spans feed
        ``sa_tick_phase_seconds`` (wall), ``sa_shard_phase_seconds_total``
        and ``sa_tick_phase_cpu_seconds_total`` (the PhaseTimer's second
        clock); sub-spans feed ``sa_tick_subphase_seconds_total`` only.
        """
        acc, shard_acc, raw, cpu = phases.drain()
        compiles, phases.compiles = phases.compiles, {}
        sub_acc, sub_raw = subphases.drain()
        total = 0.0
        for phase, secs in acc.items():
            self.m_tick_phase.observe(secs, phase)
            total += secs
        for (shard, phase), secs in shard_acc.items():
            self.m_shard_phase.inc(secs, str(shard), phase)
        for phase, secs in cpu.items():
            self.m_phase_cpu.inc(secs, phase)
        for phase, n in compiles.items():
            self.m_compiles.inc(n, phase)
        for name, secs in sub_acc.items():
            self.m_subphase.inc(secs, name)
        if total:
            self.m_tick.observe(total)
        self.m_ticks.inc(levels)
        self.m_queue_depth.set(queue_depth)
        self.m_active.set(n_active)
        used = held = 0
        for s in shards:
            used += s.pool.n_active
            held += s.pool.n_slots
            self.m_shard_slots_used.set(s.pool.n_active, str(s.index))
        self.m_slot_occupancy.set(used / held if held else 0.0)
        self.m_compile_events.series[()] = float(
            compile_events() - self.compile_events_start)
        if self.trace is not None:
            for phase, shard, t0, t1 in raw:
                self.trace.span(phase, t0, t1, shard=shard, tick=tick)
            for name, shard, t0, t1 in sub_raw:
                self.trace.span(name, t0, t1, shard=shard, tick=tick,
                                cat="subtick")

    def tenant_slot_ticks(self, req_id: int, n_slots: int) -> None:
        self.m_tenant_slot_ticks.inc(n_slots, str(req_id))


class NullTelemetry:
    """Telemetry off: every hook is a no-op, nothing is allocated."""

    enabled = False
    trace = None
    events = None
    registry = None

    __slots__ = ()

    def make_phase_timer(self, clock):
        return NULL_PHASE_TIMER

    def make_subphase_timer(self, clock):
        return NULL_PHASE_TIMER

    def decision(self, tick, kind, **fields):
        pass

    def plan(self, kind, n_actions):
        pass

    def end_tick(self, tick, phases, subphases, shards, queue_depth,
                 n_active, levels=1):
        pass

    def tenant_slot_ticks(self, req_id, n_slots):
        pass


#: The default for every engine: observability off, zero overhead.
NULL = NullTelemetry()
