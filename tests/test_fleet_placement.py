"""A fleet of whole-shard jobs: the paper's suite as ``suite-fleet4`` serves
it, one job per shard and the rest queued.

* jobs that each fill a shard run back to back, each bit-exact with its
  standalone run, whichever shard and turn it gets;
* the placement counter, the idle-shard counter and the ``admit.place``
  span agree with a hand count;
* the device fence's profiler annotation names the shard it waits on.

Everything runs on four logical shards of one CPU device."""
import jax
import numpy as np

from repro.service import (EngineConfig, SARequest, SAServeEngine, Telemetry,
                           TraceBuilder, run_standalone)

CPS = 8
N_SLOTS = 4
SHARDS = 4


def _cfg(**kw):
    return EngineConfig(n_slots=N_SLOTS, chains_per_slot=CPS,
                        n_devices=SHARDS, use_pallas=False, **kw)


def _whole_shard(req_id, objective="exponential", dim=4, levels=3, **kw):
    """A request that fills one shard; ``levels`` ladder levels (T0=10,
    rho=0.5, T_min just above the last level's temperature)."""
    return SARequest(req_id=req_id, objective=objective, dim=dim,
                     n_chains=N_SLOTS * CPS, T0=10.0, rho=0.5,
                     T_min=10.0 * 0.5 ** levels * 1.04, N=10,
                     seed=500 + req_id, **kw)


def test_whole_shard_jobs_run_back_to_back_bit_exact():
    """Eight whole-shard jobs on four shards: four run, four wait, and each
    waiting job takes the shard of the one that retires before it."""
    suite = [("exponential", 4), ("salomon", 10), ("ackley", 30),
             ("schwefel", 8)]
    reqs = [_whole_shard(i, *suite[i % len(suite)]) for i in range(8)]
    cfg = _cfg()
    engine = SAServeEngine(cfg)
    for r in reqs:
        engine.submit(r)
    results = {r.req_id: r for r in engine.run(max_ticks=100)}
    assert sorted(results) == list(range(8))
    first, second = reqs[:4], reqs[4:]
    assert [results[r.req_id].home_shard for r in first] == [0, 1, 2, 3]
    assert sorted(results[r.req_id].home_shard for r in second) == \
        [0, 1, 2, 3]
    for r in first:
        assert results[r.req_id].start_tick == 0
    for r in second:                # placed the tick after the first wave
        assert results[r.req_id].start_tick == 3
        assert results[r.req_id].finish_tick == 5
    for req in reqs:
        res, solo = results[req.req_id], run_standalone(req, cfg)
        assert res.levels_run == solo.levels_run == 3
        assert res.f_best == solo.f_best
        np.testing.assert_array_equal(res.x_best, solo.x_best)
        assert res.champion_history == solo.champion_history


def test_placements_idle_shards_and_place_spans_match_hand_counts():
    """Five whole-shard jobs: job 0 runs 2 levels, jobs 1-3 run 3, job 4
    runs 2.  Ticks 0-1: jobs 0-3 on shards 0-3.  Tick 2: job 4 takes shard
    0; jobs 1-3 run their last level.  Tick 3: only shard 0 works, so
    shards 1-3 idle once each."""
    tel = Telemetry(trace=TraceBuilder())
    engine = SAServeEngine(_cfg(), telemetry=tel)
    for i, levels in enumerate([2, 3, 3, 3, 2]):
        engine.submit(_whole_shard(i, levels=levels))
    results = {r.req_id: r for r in engine.run(max_ticks=100)}
    assert engine.tick_count == 4
    assert results[4].home_shard == 0 and results[4].start_tick == 2
    placed = tel.registry["sa_placements_total"]
    assert {k[0]: v for k, v in placed.series.items()} == \
        {"0": 2, "1": 1, "2": 1, "3": 1}
    idle = tel.registry["sa_shard_idle_ticks_total"]
    assert {k[0]: v for k, v in idle.series.items()} == \
        {"1": 1, "2": 1, "3": 1}
    doc = tel.trace.to_json()
    spans = [e for e in doc["traceEvents"]
             if e.get("cat") == "subtick" and e["name"] == "admit.place"]
    assert len(spans) == 5
    assert tel.registry["sa_tick_subphase_seconds_total"].value(
        "admit.place") > 0


def test_idle_counter_and_place_span_stay_off_without_telemetry():
    engine = SAServeEngine(_cfg())
    engine.submit(_whole_shard(0))
    engine.run(max_ticks=100)
    assert engine.telemetry.registry is None


def test_shard_phase_annotations_carry_the_shard(tmp_path):
    """``sa.device_wait`` (and the other phases opened for one shard) keep
    their names in the profiler trace and carry ``shard`` as an argument;
    the fleet-wide phases carry none."""
    from jax.profiler import ProfileData

    engine = SAServeEngine(_cfg(), telemetry=Telemetry())
    for i in range(SHARDS):
        engine.submit(_whole_shard(i, levels=3))
    engine.tick()                              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.tick()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(str(path)).planes
                if p.name == "/host:CPU")
    shards = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith("sa."):
                shards.setdefault(e.name, []).append(
                    dict(e.stats).get("shard"))
    for phase in ("sa.dispatch", "sa.device_wait", "sa.materialize"):
        assert sorted(shards[phase]) == list(range(SHARDS)), phase
    assert shards["sa.schedule"] and set(shards["sa.schedule"]) == {None}
