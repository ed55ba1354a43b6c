"""One run of one cell: set-up, the measured window on the wall clock,
the close, the correctness check and the metrics.

The entry the window drives is the server's public API: the harness
submits each request when it is due (``SAServeEngine.submit``), calls
``SAServeEngine.tick`` while there is work, and otherwise sleeps until
the next due time.  Latency runs from a request's due time to when the
harness sees its result after a tick.  Warm-up goes through the same
``submit``/``tick`` path (see :func:`warm_up`).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import check, loadgen, spec, tracereduce

#: JAX's persistent compilation cache: a fixed directory in the checkout,
#: so that only the first run of a cell there compiles.
CACHE_DIR = spec.BENCH_DIR / ".jax_cache"
#: Where a traced run keeps its profile (overwritten by the next one).
TRACE_DIR = spec.BENCH_DIR / ".traces"
#: How long after the window's close the harness waits for answers due in
#: the window before it counts them as never coming.
CLOSE_WAIT_S = 60.0
#: The closed-loop client ids are request ids below this; warm-up
#: requests take ids from here on, so that the two never meet.
WARMUP_ID0 = 1 << 40

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileCounter:
    """Backend compiles and persistent-cache hits, seen through
    ``jax.monitoring``."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_kw):
        if name == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, name, **_kw):
        if name == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def use_compile_cache(path: Path = CACHE_DIR) -> None:
    """Keep every compiled program, however quick its compile: JAX's
    default writes only those that took a second or more."""
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def profile_options() -> "jax.profiler.ProfileOptions":
    """Device ops, XLA modules and the harness's annotations, without the
    Python tracer: it records every Python call of the host loop (some
    hundred thousand events a second), which slows the traced window
    and fills the disk."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_chips(n: int) -> list:
    """The accelerator devices, or :class:`NoChip`."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's devices are {devices[0].platform} "
                     f"({devices[0].device_kind}), not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices


@dataclasses.dataclass
class Record:
    """One request of the run, on the harness's clock (perf_counter)."""

    req: object
    due: float
    submitted: float = math.nan
    observed: float = math.nan
    result: object = None
    in_window: bool = False      # due before the window closed
    judged: bool = False         # an answer is due for the check
    late_start: object = None    # (level, chain states) held at the close

    @property
    def latency(self) -> float:
        """Due -> seen; inf when no completed answer came."""
        if self.result is None or not self.result.completed:
            return math.inf
        return self.observed - self.due


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take it."""

    cell: spec.Cell
    setup_s: float
    t0: float
    t_close: float
    records: List[Record]
    ticks: int                   # engine ticks inside the window
    job_levels: List[tuple]      # (objective, dim, N, chains, levels) of
                                 # work done inside the window
    phases: Dict[str, float]     # telemetry phase seconds in the window
    trace: Optional[dict]        # tracereduce summary of the window
    peaks: Optional[dict]

    @property
    def window_s(self) -> float:
        return self.t_close - self.t0

    def window_records(self) -> List[Record]:
        return [r for r in self.records if r.in_window]


def _jobs(engine):
    for shard in engine.shards:
        yield from shard.jobs.values()


def warm_up(engine, config: dict, traffic: dict, n_devices: int) -> int:
    """Compile every program the traffic can use, through submit/tick.

    For each request shape the traffic can make a group of, submit one
    one-level request per shard at once (placement puts one on each
    shard, the least loaded first), then tick until they are done.
    Returns the number of warm-up requests served.
    """
    clients = traffic.get("clients", math.inf)
    rid = WARMUP_ID0
    n = 0
    for _slots, req in loadgen.warmup_requests(config, clients):
        for _ in range(n_devices):
            engine.submit(dataclasses.replace(req, req_id=rid))
            rid += 1
            n += 1
        while not engine.done:
            engine.tick()
    return n


def _phase_sums(engine) -> Dict[str, float]:
    agg = engine.stats()["phases"].get("aggregate", {})
    return {p: s.get("sum", 0.0) for p, s in agg.items()}


def serve_window(engine, gen, traffic, seconds, ann, start_trace=None):
    """The measured window and its close.  Returns the records, the window
    bounds, the tick count and the work done inside the window."""
    now = time.perf_counter
    loop = traffic["loop"]
    records: Dict[int, Record] = {}
    n_seen = len(engine.results)
    queue = collections.deque()          # (due, req) not yet submitted
    if loop == "open":
        schedule = gen.open_schedule(seconds)
    else:
        stream = gen.closed_stream()

    def submit_due(t):
        while queue and queue[0][0] <= t:
            due, req = queue.popleft()
            records[req.req_id] = Record(req=req, due=due, submitted=now(),
                                         in_window=due < t_end)
            with ann("bench.submit"):
                engine.submit(req, arrival_time=engine.tick_count)

    def observe(t_obs):
        nonlocal n_seen
        with ann("bench.observe"):
            for res in engine.results[n_seen:]:
                rec = records.get(res.req_id)
                if rec is None:
                    continue
                rec.result, rec.observed = res, t_obs
                if loop == "closed" and not closing:
                    queue.append((t_obs, next(stream)))
            n_seen = len(engine.results)

    def refill_open(until):
        while not queue or queue[-1][0] < until:
            off, req = next(schedule)
            queue.append((t0 + off, req))

    if start_trace is not None:
        start_trace()
    closing = False
    t0 = now()
    t_end = t0 + seconds
    if loop == "open":
        refill_open(t_end)
    else:
        for _ in range(int(traffic["clients"])):
            queue.append((t0, next(stream)))
    ticks = 0
    with ann("bench.window"):
        while True:
            t = now()
            submit_due(t)
            if t >= t_end:
                break
            if not engine.done:
                with ann("bench.tick"):
                    engine.tick()
                ticks += 1
                observe(now())
            else:
                with ann("bench.sleep"):
                    nxt = queue[0][0] if queue else t_end
                    time.sleep(max(0.0, min(nxt, t_end) - now()))
    t_close = now()
    closing = True
    levels = {j.req.req_id: (j.req, j.granted_chains, j.level)
              for j in _jobs(engine)}
    return records, t0, t_close, ticks, levels, queue, refill_open, observe


def _work(records, levels_at_close) -> List[tuple]:
    """(objective, dim, N, chains, levels) done inside the window."""
    out = []
    for rec in records.values():
        res = rec.result
        if res is not None and res.completed:
            out.append((rec.req, res.granted_chains, res.levels_run))
    for req, chains, level in levels_at_close.values():
        out.append((req, chains, level))
    return [(r.objective, r.dim, r.N, c, lv) for r, c, lv in out if lv]


def _close(engine, traffic, checks, records, queue, refill_open, observe,
           t_close):
    """After the window: mark the answers due for the check, and serve
    until they have come (or :data:`CLOSE_WAIT_S` has passed).

    * ``stop``: the answers the window completed;
    * ``truncate``: those, and every job in flight, which keeps the chain
      states it holds at the close for the check and ends after
      ``late_levels`` (at least one) more levels, through the public
      operator entry point;
    * ``drain``: every request due in the window; the open loop keeps
      offering load meanwhile.
    """
    now = time.perf_counter
    how = traffic["at_close"]
    for r in records.values():
        r.judged = (r.in_window if how == "drain" else
                    r.result is not None and r.observed <= t_close)
    if how == "stop":
        return
    if how == "truncate":
        more = int(checks.get("late_levels", 0))
        for shard in engine.shards:
            for job in list(shard.jobs.values()):
                rec = records[job.req.req_id]
                rec.judged = True
                if more:
                    rec.late_start = (job.level, np.concatenate(
                        shard.pool.checkpoint(job.rid)))
                engine.truncate_active(job.req.req_id,
                                       job.level + max(more, 1))
    deadline = t_close + CLOSE_WAIT_S
    while (any(r.judged and r.result is None for r in records.values())
           and now() < deadline):
        if how == "drain":
            refill_open(now() + 1.0)
            t = now()
            while queue and queue[0][0] <= t:
                due, req = queue.popleft()
                engine.submit(req, arrival_time=engine.tick_count)
                records[req.req_id] = Record(req=req, due=due, submitted=now())
        if engine.done:
            nxt = queue[0][0] if queue else deadline
            time.sleep(max(0.0, min(nxt, deadline) - now()))
            continue
        engine.tick()
        observe(now())


def _memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             log=sys.stderr) -> dict:
    """One run of ``cell``: the result line as a dict.

    ``require_chip=False`` (tests only) runs on whatever JAX has, without
    the persistent compile cache or the peaks table."""
    if require_chip:
        use_compile_cache()
    counter = CompileCounter()
    try:
        return _run_cell(cell, seed, seconds, trace, t_start, require_chip,
                         log, counter)
    finally:
        counter.close()


def _run_cell(cell, seed, seconds, trace, t_start, require_chip, log,
              counter) -> dict:
    from repro.service.engine import EngineConfig, SAServeEngine
    from repro.service.telemetry import Telemetry

    n_dev = int(cell.config["engine"].get("n_devices", 1))
    devices = (find_chips(cell.chips) if require_chip else jax.devices())
    dev = devices[0]
    engine = SAServeEngine(EngineConfig(**cell.config["engine"]),
                           telemetry=Telemetry() if trace else None)
    if require_chip and (not engine.use_pallas or engine.cfg.interpret):
        raise NoChip(f"the engine resolved use_pallas={engine.use_pallas}, "
                     f"interpret={engine.cfg.interpret}")
    placed = {s.device for s in engine.shards}
    if require_chip and len(placed) != n_dev:
        raise NoChip(f"{n_dev} shards sit on {len(placed)} devices")
    n_warm = warm_up(engine, cell.config, cell.traffic, n_dev)
    gc.collect()
    setup_s = time.perf_counter() - t_start
    print(f"[bench] {cell.name}: set-up {setup_s:.3f} s, {n_warm} warm-up "
          f"requests, {counter.compiles} compiles "
          f"({counter.compile_s:.3f} s), {counter.cache_hits} cache hits",
          file=log)

    gen = loadgen.Generator(cell.config, cell.traffic, seed)
    ann = jax.profiler.TraceAnnotation if trace else no_annotation
    start_trace = None
    if trace:
        shutil.rmtree(TRACE_DIR / cell.name, ignore_errors=True)

        def start_trace():
            jax.profiler.start_trace(str(TRACE_DIR / cell.name),
                                     profiler_options=profile_options())
    compiles0 = counter.compiles + counter.cache_hits
    phases0 = _phase_sums(engine) if trace else {}
    (records, t0, t_close, ticks, levels, queue, refill_open,
     observe) = serve_window(engine, gen, cell.traffic, seconds, ann, start_trace)
    reduced = None
    if trace:
        phases = {p: s - phases0.get(p, 0.0)
                  for p, s in _phase_sums(engine).items()}
        jax.profiler.stop_trace()
        reduced = tracereduce.reduce_dir(TRACE_DIR / cell.name,
                                         n_devices=n_dev)
    else:
        phases = {}
    compiles_in_window = counter.compiles + counter.cache_hits - compiles0
    print(f"[bench] compiles_in_window={compiles_in_window}", file=log)
    job_levels = _work(records, levels)
    _close(engine, cell.traffic, cell.checks, records, queue, refill_open,
           observe, t_close)
    memory = _memory_peak(sorted(placed, key=lambda d: d.id))
    run = Run(cell=cell, setup_s=setup_s, t0=t0, t_close=t_close,
              records=list(records.values()), ticks=ticks,
              job_levels=job_levels,
              phases=phases, trace=reduced,
              peaks=spec.peaks(dev.device_kind) if require_chip else None)
    del engine, queue, refill_open, observe
    gc.collect()

    verdict = check.check(run, spec.reference(cell.config["family"]))
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": verdict.correct, "attempted": verdict.attempted,
           "failed": verdict.failed, "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    for line in verdict.lines():
        print(line, file=log)
    out["checks"] = verdict.numbers
    return out


@contextlib.contextmanager
def no_annotation(_name):
    yield
