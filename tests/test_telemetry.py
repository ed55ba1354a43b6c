"""Observability subsystem: telemetry, tracing, event log (PR 6).

Tentpole guarantees:

* **zero overhead off**: with telemetry disabled (the default) the
  engine allocates no span objects (phases or sub-spans) and compiles
  no extra programs;
* **bit-exact on**: enabling metrics + tracing + the event log perturbs
  no trajectory — champion histories match the disabled run and the
  standalone oracle at every ladder level;
* **trace contract**: ``--trace`` output validates against the
  checked-in schema (trace_schema.json) and uses only the tick-phase
  taxonomy;
* **metrics survive the elastic fleet**: a retired shard's per-shard
  series are still present after drain/resize;
* **decision log is a regression oracle**: the same seeded run produces
  a byte-identical JSONL stream, replayable against a fresh run.

Everything runs on logical shards (tier-1); the CI multi-device job
re-runs the CLI smoke with 4 real XLA host devices.
"""

import json

import jax
import numpy as np
import pytest

from repro.service import (
    ArrivalProcess,
    EngineConfig,
    EventLog,
    PhaseTimer,
    SARequest,
    SAServeEngine,
    SchedulerConfig,
    SubPhaseTimer,
    Telemetry,
    TICK_PHASES,
    TICK_SUBPHASES,
    TraceBuilder,
    compile_events,
    run_standalone,
    validate_trace,
)
from repro.objectives.families import get_family
from repro.service.engine import _group_tick
from repro.service.telemetry import (Histogram, MetricsRegistry,
                                     compile_seconds)

CPS = 8


def _cfg(n_slots=4, n_devices=1, **kw):
    return EngineConfig(n_slots=n_slots, chains_per_slot=CPS,
                        n_devices=n_devices, **kw)


def _req(req_id, objective="rastrigin", dim=4, n_chains=CPS, seed=None,
         **kw):
    kw.setdefault("T0", 10.0)
    kw.setdefault("T_min", 1.0)
    kw.setdefault("rho", 0.7)
    kw.setdefault("N", 10)
    return SARequest(req_id=req_id, objective=objective, dim=dim,
                     n_chains=n_chains,
                     seed=100 + req_id if seed is None else seed, **kw)


def _mix(n=4):
    objs = ["rastrigin", "ackley", "griewank", "schwefel"]
    return [_req(i, objective=objs[i % len(objs)], priority=i % 2)
            for i in range(n)]


def _serve(telemetry=None, n=4, n_devices=1, **cfg_kw):
    engine = SAServeEngine(_cfg(n_devices=n_devices, **cfg_kw),
                           telemetry=telemetry)
    for r in _mix(n):
        engine.submit(r)
    results = engine.run(max_ticks=400)
    return engine, {r.req_id: r for r in results}


# ------------------------------------------------------------ disabled path
def test_disabled_allocates_no_spans_and_compiles_nothing_extra():
    compile_before = compile_events()
    spans_before = PhaseTimer.spans_entered
    subs_before = SubPhaseTimer.spans_entered
    engine, results = _serve()
    assert len(results) == 4
    # The zero-overhead witness: the class-wide span counters never moved.
    assert PhaseTimer.spans_entered == spans_before
    assert SubPhaseTimer.spans_entered == subs_before
    # And the engine defaults hold: no registry, no trace, no events.
    assert engine.telemetry.enabled is False
    assert engine.telemetry.registry is None
    compile_disabled = compile_events() - compile_before

    # Enabled run: identical config => no *additional* backend programs
    # beyond what the disabled run compiled (telemetry adds zero).
    before = compile_events()
    _serve(Telemetry(trace=TraceBuilder(), events=EventLog()))
    assert compile_events() - before <= compile_disabled


def test_compile_hook_counts_events_and_seconds():
    """One fresh program is one backend compile, and its seconds add up."""
    events, secs = compile_events(), compile_seconds()
    jax.jit(lambda a: a * 3.0 + 1.0)(np.arange(7, dtype=np.float32))
    assert compile_events() - events == 1
    assert compile_seconds() > secs


def test_enabled_compiles_no_extra_group_programs():
    if not (hasattr(_group_tick, "clear_cache")
            and hasattr(_group_tick, "_cache_size")):
        pytest.skip("kernel cache introspection unavailable")
    _group_tick.clear_cache()
    _serve()
    baseline = _group_tick._cache_size()
    _group_tick.clear_cache()
    _serve(Telemetry(trace=TraceBuilder(), events=EventLog()))
    assert _group_tick._cache_size() == baseline


# ------------------------------------------------------------- bit-exactness
def test_enabled_is_bit_exact_at_every_level():
    _, plain = _serve()
    tel = Telemetry(trace=TraceBuilder(), events=EventLog())
    _, traced = _serve(tel)
    assert plain.keys() == traced.keys()
    for rid in plain:
        a, b = plain[rid], traced[rid]
        # Whole champion trajectory, level by level — not just the final f.
        assert a.champion_history == b.champion_history
        assert a.f_best == b.f_best
        assert a.finish_tick == b.finish_tick
        assert a.finish_reason == b.finish_reason
    # And against the standalone oracle (the --check invariant).
    cfg = _cfg()
    for req in _mix(4):
        solo = run_standalone(req, cfg)
        assert traced[req.req_id].f_best == solo.f_best
        assert traced[req.req_id].champion_history == solo.champion_history


def test_enabled_is_bit_exact_under_preemption_and_shards():
    def serve(tel):
        cfg = _cfg(n_slots=2, n_devices=2, scheduler=SchedulerConfig(
            policy="priority", overload="preempt", preemption_budget=1))
        engine = SAServeEngine(cfg, telemetry=tel)
        reqs = [_req(i, priority=i % 3, on_overload="preempt")
                for i in range(6)]
        arrivals = ArrivalProcess.poisson(reqs, rate=0.7, seed=7)
        res = {r.req_id: r for r in
               engine.run_stream(arrivals, max_ticks=400)}
        return engine, res

    _, plain = serve(None)
    engine, traced = serve(Telemetry(trace=TraceBuilder(),
                                     events=EventLog()))
    assert plain.keys() == traced.keys()
    for rid in plain:
        assert plain[rid].champion_history == traced[rid].champion_history
        assert plain[rid].finish_tick == traced[rid].finish_tick


# ------------------------------------------------------------------ tracing
def test_trace_validates_against_checked_in_schema():
    tel = Telemetry(trace=TraceBuilder())
    engine, results = _serve(tel, n_devices=2)
    doc = tel.trace.to_json()
    assert validate_trace(doc) == []
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "M", "b", "e"} <= phs
    # Per-shard phase spans landed on per-shard tracks (tid shard+1).
    tick_spans = [e for e in doc["traceEvents"] if e.get("cat") == "tick"]
    assert {e["name"] for e in tick_spans} <= set(TICK_PHASES)
    assert {e["tid"] for e in tick_spans} >= {0, 1, 2}
    # Every request has a begin and a terminal end on its async track.
    for rid in results:
        evs = [e for e in doc["traceEvents"]
               if e.get("cat") == "request" and e.get("id") == rid]
        assert [e["ph"] for e in evs][0] == "b"
        assert [e["ph"] for e in evs][-1] == "e"
    # The document round-trips through real JSON.
    assert validate_trace(json.loads(tel.trace.dumps())) == []


def test_trace_schema_rejects_malformed_events():
    assert validate_trace({"traceEvents": "nope"}) != []
    bad_ph = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 0, "tid": 0}],
        "displayTimeUnit": "ms"}
    assert any("not in" in e for e in validate_trace(bad_ph))
    bad_phase = {"traceEvents": [
        {"ph": "X", "name": "warp", "cat": "tick", "pid": 0, "tid": 0,
         "ts": 0, "dur": 1}], "displayTimeUnit": "ms"}
    assert any("unknown tick phase" in e for e in validate_trace(bad_phase))


# -------------------------------------------------------------- metrics
def test_phase_metrics_cover_the_taxonomy():
    tel = Telemetry()
    engine, _ = _serve(tel)
    snap = tel.registry.snapshot()
    phases = {k.split("=", 1)[1]
              for k in snap["sa_tick_phase_seconds"]["series"]}
    assert phases == set(TICK_PHASES)
    for summary in snap["sa_tick_phase_seconds"]["series"].values():
        assert summary["count"] > 0
        assert summary["p50"] <= summary["p90"] <= summary["p99"]
    assert snap["sa_ticks_total"]["series"][""] == engine.tick_count
    # stats() mirrors the same data for humans.
    st = engine.stats()
    assert set(st["phases"]["aggregate"]) == set(TICK_PHASES)
    assert st["phases"]["per_shard"]["0"]["dispatch"] > 0
    assert st["phases"]["per_shard"]["0"] == {
        phase: secs for (shard, phase), secs
        in tel.registry["sa_shard_phase_seconds_total"].series.items()
        if shard == "0"}


def test_phase_timer_tracks_host_cpu_alongside_wall():
    import time

    t = PhaseTimer(time.perf_counter)
    with t("dispatch", shard=0):
        sum(range(50_000))        # burn host CPU: cpu time must register
    acc, shard_acc, raw, cpu = t.drain()
    assert set(cpu) == {"dispatch"}
    # One thread's CPU time can never exceed the span's wall time.
    assert 0.0 <= cpu["dispatch"] <= acc["dispatch"] + 1e-3
    # drain() resets both clocks.
    assert t.drain() == ({}, {}, [], {})


def test_phase_cpu_metric_covers_host_phases_and_stats():
    tel = Telemetry()
    engine, _ = _serve(tel)
    cpu = engine.stats()["phases"]["cpu_seconds"]
    wall = {p: s["sum"]
            for p, s in engine.stats()["phases"]["aggregate"].items()}
    # The launch path burned host CPU, and the registry mirrors stats().
    assert cpu["dispatch"] > 0
    assert cpu == {p: secs for (p,), secs
                   in tel.registry["sa_tick_phase_cpu_seconds_total"]
                   .series.items()}
    # Run-total host CPU per phase is bounded by the wall spans it ran in
    # (thread_time of one thread cannot exceed elapsed wall).
    for phase, secs in cpu.items():
        assert secs <= wall[phase] + 1e-2


def test_metrics_survive_drain_and_resize():
    tel = Telemetry(events=EventLog())
    cfg = _cfg(n_slots=2, n_devices=3, migration_budget=2)
    engine = SAServeEngine(cfg, telemetry=tel)
    for r in _mix(6):
        engine.submit(r)
    for _ in range(3):
        engine.tick()
    victim = max(s.index for s in engine.live_shards)
    engine.drain(victim)
    engine.run(max_ticks=400)
    assert any(i == victim for i, _ in engine.retired_shards)
    # The retired shard's per-shard series are still in the registry...
    used = tel.registry["sa_shard_slots_used"]
    assert (str(victim),) in used.series
    phase_keys = {k for k in tel.registry["sa_shard_phase_seconds_total"]
                  .series if k[0] == str(victim)}
    assert phase_keys
    # ...and stats() reads them from there, so they outlive the shard.
    assert set(engine.stats()["phases"]["per_shard"][str(victim)]) == \
        {phase for _, phase in phase_keys}
    # ...and its lifecycle shows up in decisions + events.
    decisions = tel.registry["sa_scheduler_decisions_total"]
    assert decisions.value("drain") == 1
    assert decisions.value("shard_retired") == 1
    kinds = {r["event"] for r in tel.events.records}
    assert {"admit", "drain", "shard_retired"} <= kinds
    # Growing again afterwards keeps old series and adds new ones.
    engine.add_shards(1)
    assert decisions.value("shard_added") == 1


def test_prometheus_exposition_and_histogram_quantiles():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "Requests", ("status",))
    c.inc(3, "ok")
    c.inc(1, "err")
    h = reg.histogram("latency_seconds", "Latency")
    for ms in range(1, 101):
        h.observe(ms / 1000.0)
    # Exponential-bucket quantile error is bounded by the growth factor.
    assert h.quantile(0.5) == pytest.approx(0.050, rel=0.15)
    assert h.quantile(0.99) == pytest.approx(0.099, rel=0.15)
    assert h.summary()["count"] == 100
    text = reg.exposition()
    assert '# TYPE requests_total counter' in text
    assert 'requests_total{status="ok"} 3' in text
    assert 'latency_seconds{quantile="0.5"}' in text
    assert 'latency_seconds_count 100' in text
    # Idempotent re-registration returns the same series; conflicts raise.
    assert reg.counter("requests_total", labels=("status",)) is c
    with pytest.raises(ValueError):
        reg.gauge("requests_total")
    with pytest.raises(ValueError):
        c.inc(-1, "ok")


# ------------------------------------------------------------- event log
def test_event_log_is_deterministic_and_replayable():
    def serve():
        tel = Telemetry(events=EventLog())
        cfg = _cfg(n_slots=2, n_devices=2, scheduler=SchedulerConfig(
            policy="priority", overload="preempt"))
        engine = SAServeEngine(cfg, telemetry=tel)
        reqs = [_req(i, priority=i % 3, on_overload="preempt")
                for i in range(5)]
        engine.run_stream(ArrivalProcess.poisson(reqs, rate=0.8, seed=3),
                          max_ticks=400)
        return tel.events

    log_a, log_b = serve(), serve()
    # Byte-identical run-to-run: the scheduler-decision regression oracle.
    assert log_a.dumps() == log_b.dumps()
    records = EventLog.loads(log_a.dumps())
    assert records == log_a.records
    # Tick-clock fields only: no wall-clock key may leak in.
    for rec in records:
        assert "wall" not in json.dumps(rec)
        assert rec["tick"] >= 0
    kinds = {r["event"] for r in records}
    assert "admit" in kinds and "retire" in kinds


# ----------------------------------------------------- macro-tick fusion
def test_macro_tick_disabled_telemetry_allocates_zero_spans():
    """The zero-overhead guarantee survives fusion: a K=4 run with
    telemetry off never enters a span or a sub-span."""
    spans_before = PhaseTimer.spans_entered
    subs_before = SubPhaseTimer.spans_entered
    engine, results = _serve(macro_k=4)
    assert len(results) == 4
    assert PhaseTimer.spans_entered == spans_before
    assert SubPhaseTimer.spans_entered == subs_before
    assert engine.telemetry.enabled is False


def test_macro_tick_phases_cover_taxonomy_and_level_clock():
    """At K>1 the per-tick spans still cover the whole phase taxonomy
    (device_wait fences the fused K-level program; dispatch is the host
    pack+launch), and sa_ticks_total stays on the ladder-level clock —
    equal to tick_count, which counts levels, not launches."""
    tel = Telemetry()
    engine, _ = _serve(tel, macro_k=4)
    snap = tel.registry.snapshot()
    phases = {k.split("=", 1)[1]
              for k in snap["sa_tick_phase_seconds"]["series"]}
    assert phases == set(TICK_PHASES)
    for summary in snap["sa_tick_phase_seconds"]["series"].values():
        assert summary["count"] > 0
    assert snap["sa_ticks_total"]["series"][""] == engine.tick_count
    # Far fewer launches than levels: the fusion actually engaged.
    assert engine.group_launches < engine.tick_count


def test_macro_tick_event_log_deterministic_and_boundary_stamped():
    """The decision log stays byte-identical run-to-run at K=4, and every
    decision is stamped with the macro-tick-boundary tick clock (this
    closed-loop mix runs uncontended, so boundaries sit at multiples of
    K until the final partial macro-tick — no decision may carry an
    intra-macro-tick timestamp)."""
    def serve():
        tel = Telemetry(events=EventLog())
        engine, _ = _serve(tel, macro_k=4)
        return tel.events

    log_a, log_b = serve(), serve()
    assert log_a.dumps() == log_b.dumps()
    records = EventLog.loads(log_a.dumps())
    assert {r["event"] for r in records} >= {"admit", "retire"}
    for rec in records:
        assert rec["tick"] % 4 == 0, "decision stamped off a boundary"


def test_macro_tick_trace_validates_and_is_bit_exact():
    tel = Telemetry(trace=TraceBuilder(), events=EventLog())
    _, plain = _serve(macro_k=4)
    engine, traced = _serve(tel, macro_k=4)
    assert plain.keys() == traced.keys()
    for rid in plain:
        assert plain[rid].champion_history == traced[rid].champion_history
        assert plain[rid].finish_tick == traced[rid].finish_tick
    doc = tel.trace.to_json()
    assert validate_trace(doc) == []
    tick_spans = [e for e in doc["traceEvents"] if e.get("cat") == "tick"]
    assert {e["name"] for e in tick_spans} <= set(TICK_PHASES)


# ------------------------------------------------------------------ CLI
def test_serve_sa_cli_trace_events_metrics(tmp_path, capsys):
    from repro.service import serve_sa
    trace_p = tmp_path / "trace.json"
    events_p = tmp_path / "events.jsonl"
    metrics_p = tmp_path / "metrics.prom"
    serve_sa.main([
        "--requests", "3", "--slots", "2", "--chains-per-slot", "8",
        "--max-ticks", "200", "--json",
        "--trace", str(trace_p), "--events", str(events_p),
        "--metrics", str(metrics_p)])
    doc = json.loads(capsys.readouterr().out)
    # --check ran (default) and passed bit-exact with telemetry on.
    assert doc["check"]["bit_exact"] == doc["check"]["served"] == 3
    assert "sa_tick_phase_seconds" in doc["metrics"]
    trace = json.loads(trace_p.read_text())
    assert validate_trace(trace) == []
    assert len(EventLog.loads(events_p.read_text())) > 0
    assert "# TYPE sa_ticks_total counter" in metrics_p.read_text()


# ------------------------------------------------- sub-spans and counters
def _one_group(tel, macro_k=1, n_levels_cut=None):
    """One request of three slots: a 3-block group padded to 4."""
    engine = SAServeEngine(_cfg(macro_k=macro_k), telemetry=tel)
    engine.submit(_req(0, n_chains=3 * CPS))
    if n_levels_cut is not None:
        engine.tick()
        engine.truncate_active(0, n_levels_cut)
    res = engine.run(max_ticks=400)[0]
    return engine, res


def test_subspans_enter_and_stay_out_of_the_phase_sums():
    tel = Telemetry(trace=TraceBuilder())
    subs_before = SubPhaseTimer.spans_entered
    engine, _ = _one_group(tel)
    assert SubPhaseTimer.spans_entered > subs_before     # the witness is live
    sub = tel.registry["sa_tick_subphase_seconds_total"]
    got = {name for (name,) in sub.series}
    assert got == {"admit.place", "admit.init_state", "dispatch.pack",
                   "dispatch.h2d", "dispatch.launch", "materialize.d2h",
                   "materialize.fold"}
    assert got <= set(TICK_SUBPHASES)
    # The phase histogram holds the six phases and nothing else.
    phases = {p for (p,) in tel.registry["sa_tick_phase_seconds"].series}
    assert phases == set(TICK_PHASES)
    assert set(engine.stats()["phases"]["aggregate"]) == set(TICK_PHASES)
    # A sub-span lies inside its phase: the sums cannot exceed the phase's.
    agg = engine.stats()["phases"]["aggregate"]
    for name, in sub.series:
        phase = name.split(".")[0]
        assert sub.value(name) <= agg[phase]["sum"] + 1e-6
    # In the trace document each sub-span nests in a span of its phase on
    # the same track.
    doc = tel.trace.to_json()
    assert validate_trace(doc) == []
    spans = [e for e in doc["traceEvents"] if e.get("cat") == "tick"]
    subs = [e for e in doc["traceEvents"] if e.get("cat") == "subtick"]
    assert {e["name"] for e in subs} == got
    for e in subs:
        parent = e["name"].split(".")[0]
        assert any(p["name"] == parent and p["tid"] == e["tid"]
                   and p["ts"] <= e["ts"] + 1e-3
                   and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3
                   for p in spans), e


def test_trace_schema_rejects_an_unknown_subspan():
    bad = {"traceEvents": [
        {"ph": "X", "name": "dispatch.warp", "cat": "subtick", "pid": 0,
         "tid": 1, "ts": 0, "dur": 1}], "displayTimeUnit": "ms"}
    assert any("unknown tick sub-span" in e for e in validate_trace(bad))


def test_state_bytes_and_block_steps_match_hand_counts_at_k1():
    tel = Telemetry()
    engine, res = _one_group(tel)
    levels, n_steps, dim = res.levels_run, 10, 4
    assert levels == engine.group_launches > 1
    per_launch = 4 * CPS * dim * 4            # n_padded x cps x dim x f32
    state = tel.registry["sa_state_bytes_total"]
    # The state goes up once and stays on the device while the job runs.
    assert state.value("h2d") == per_launch
    assert state.value("d2h") == 0
    steps = tel.registry["sa_block_steps_total"]
    assert steps.value("live") == levels * 3 * n_steps
    assert steps.value("padded") == levels * 1 * n_steps
    assert steps.value("dead") == 0
    # K=1 shares the fused path's buffer cache: packed once, then reused.
    buf = tel.registry["sa_state_buffer_total"]
    assert (buf.value("repack"), buf.value("hit")) == (1, levels - 1)


def test_block_steps_and_buffer_cache_match_hand_counts_at_k4():
    """K=4 over a ladder cut to 6 levels: the first macro-tick runs 4 live
    levels, the second 2 live and 2 dead; the state is packed once
    (repack) and reused (hit) after."""
    tel = Telemetry()
    engine, res = _one_group(tel, macro_k=4, n_levels_cut=6)
    n_steps, dim = 10, 4
    assert res.levels_run == 6 and engine.group_launches == 2
    steps = tel.registry["sa_block_steps_total"]
    assert steps.value("live") == 3 * 6 * n_steps
    assert steps.value("dead") == 3 * 2 * n_steps
    assert steps.value("padded") == 1 * 2 * 4 * n_steps
    buf = tel.registry["sa_state_buffer_total"]
    assert (buf.value("repack"), buf.value("hit")) == (1, 1)
    state = tel.registry["sa_state_bytes_total"]
    assert state.value("h2d") == 4 * CPS * dim * 4
    assert state.value("d2h") == 0            # the state stayed on device


def test_compiles_are_counted_in_the_phase_that_ran_them():
    _group_tick.clear_cache()
    tel = Telemetry()
    _one_group(tel)
    compiles = tel.registry["sa_compiles_total"]
    assert compiles.value("dispatch") >= 1     # the group program's compile
    assert sum(compiles.series.values()) <= \
        tel.registry["sa_jax_compile_events_total"].value()


def test_device_scopes_name_the_group_program_stages():
    """The scopes are in the lowered program's op metadata: the sweep, the
    controls, the exchange and each exchange stage — with either family's
    sweep inside the one group program."""
    cps, nb, k, dim = CPS, 4, 2, 4
    n = nb * cps
    f32, u32, i32 = np.float32, np.uint32, np.int32
    family_ops = {"continuous": (np.zeros(nb, i32),),
                  "permutation": (np.zeros((nb * dim, dim), f32),) * 2}
    for family, fam_ops in family_ops.items():
        fam = get_family(family)
        args = (np.zeros((n, dim), fam.state_dtype), fam, fam_ops,
                np.ones((k, nb), f32), np.zeros((k, nb), f32),
                np.zeros(nb, u32), np.zeros(nb, u32), np.zeros(nb, u32),
                np.ones(nb, i32), np.zeros(nb, u32), np.zeros(n, i32),
                np.ones(n, bool), np.zeros(n, np.int8), np.ones(n, f32),
                np.tile(np.arange(n, dtype=i32), (2, 1)),
                np.zeros((2, n), u32), np.arange(n, dtype=i32),
                np.arange(1, n + 1, dtype=i32))
        text = _group_tick.lower(
            *args, n_steps=10, blk=cps, variant="delta", use_pallas=False,
            interpret=False, num_segments=5).as_text(debug_info=True)
        for scope in ("sa.sweep", "sa.controls", "sa.exchange/champion",
                      "sa.exchange/adopt", "sa.exchange/pt_swap",
                      "sa.exchange/pa_resample"):
            assert scope in text, (family, scope)


def test_profiler_trace_holds_the_phase_and_subspan_annotations(tmp_path):
    from jax.profiler import ProfileData

    engine = SAServeEngine(_cfg(), telemetry=Telemetry())
    engine.submit(_req(0, n_chains=3 * CPS))
    engine.tick()                              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.tick()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(str(path)).planes
                if p.name == "/host:CPU")
    names = {e.name for line in host.lines for e in line.events}
    assert {"sa.dispatch", "sa.dispatch.h2d", "sa.materialize.d2h",
            "sa.device_wait", "sa.dispatch.launch"} <= names
