"""Sharded slot pool: home-shard placement, Russkov-style migration, and
the serving-clock / terminal-accounting / target-error satellite fixes.

Tentpole guarantees (PR 4):

* **home-shard placement invariance**: a request is bit-exact versus its
  standalone single-device run no matter which shard the scheduler homed
  it on;
* **migration == uninterrupted run**: a request checkpointed off one
  shard and restored on another — at *every* temperature level of its
  ladder — produces the same best value, best x and per-level champion
  trajectory as never having moved;
* **scheduler rebalance**: when the queue head fits on no single shard
  but the pool as a whole has room, bounded cross-shard migration defrags
  the pool and seats the head, with no slot leaks or double-placements;
* **capacity scales**: the same seeded stream completes strictly more
  work by a fixed horizon on a 4-shard pool than on 1 shard.

The shards are *logical* on a single-device host (round-robin over
``jax.devices()``), so every test here runs in tier-1; the CI
multi-device job re-runs the file under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` where each shard
owns a real XLA host device.
"""
import dataclasses
import types
import time as _time

import numpy as np
import pytest

from repro.service import (ArrivalProcess, EngineConfig, RequestResult,
                           SARequest, SAServeEngine, SchedulerConfig,
                           latency_summary, run_standalone)

CPS = 8


def _req(req_id, **kw):
    kw.setdefault("objective", "rastrigin")
    kw.setdefault("dim", 4)
    kw.setdefault("n_chains", CPS)
    kw.setdefault("T0", 50.0)
    kw.setdefault("T_min", 1.0)
    kw.setdefault("rho", 0.55)   # 7-level ladder
    kw.setdefault("N", 10)
    return SARequest(req_id=req_id, seed=100 + req_id, **kw)


def _cfg(n_slots=2, n_devices=2, **kw):
    return EngineConfig(n_slots=n_slots, chains_per_slot=CPS,
                        n_devices=n_devices, use_pallas=False, **kw)


def _assert_bit_exact(res, solo):
    assert res.f_best == solo.f_best
    np.testing.assert_array_equal(res.x_best, solo.x_best)
    assert res.levels_run == solo.levels_run
    assert res.champion_history == solo.champion_history


# ------------------------------------------------------ home-shard placement
def test_requests_spread_across_shards_and_stay_bit_exact():
    """Placement invariance: requests homed on different shards are each
    bit-exact vs their standalone single-device run."""
    cfg = _cfg(n_slots=1, n_devices=3)
    engine = SAServeEngine(cfg)
    reqs = [_req(i, objective=obj)
            for i, obj in enumerate(
                ["rastrigin", "ackley", "schwefel", "griewank", "rastrigin"])]
    for r in reqs:
        engine.submit(r)
    results = {r.req_id: r for r in engine.run(max_ticks=300)}
    assert len(results) == 5
    homes = {results[i].home_shard for i in range(5)}
    assert homes == {0, 1, 2}, "placement never used some shard"
    for req in reqs:
        _assert_bit_exact(results[req.req_id], run_standalone(req, cfg))


def test_same_request_bit_exact_on_every_home_shard():
    """Force one request onto each shard in turn (by pre-filling the
    others) — its champion trajectory is identical everywhere."""
    cfg = _cfg(n_slots=1, n_devices=3)
    probe = _req(0)
    runs = []
    for target in range(3):
        engine = SAServeEngine(cfg)
        # `target` higher-priority fillers claim shards 0..target-1 first
        # (deterministic least-loaded placement), homing the probe on
        # shard `target`.
        for j in range(target):
            engine.submit(_req(10 + j, priority=9, rho=0.5, T0=8.0))
        engine.submit(probe)
        results = {r.req_id: r for r in engine.run(max_ticks=300)}
        assert results[0].home_shard == target
        runs.append(results[0])
    solo = run_standalone(probe, cfg)
    for res in runs:
        _assert_bit_exact(res, solo)


def test_placement_prefers_least_loaded_shard():
    """A request admitted while one shard is busy homes on the free one."""
    engine = SAServeEngine(_cfg(n_slots=2, n_devices=2))
    engine.submit(_req(0, rho=0.9))          # long ladder, -> shard 0
    engine.tick()
    engine.submit(_req(1, rho=0.5, T0=8.0))
    engine.tick()
    jobs = {j.req.req_id: j for _, j in engine._iter_jobs()}
    assert jobs[0].home_shard == 0
    assert jobs[1].home_shard == 1           # emptier shard scanned first


# ------------------------------------------------------------- migration
def test_migration_bit_exact_at_every_level():
    """Acceptance criterion: checkpoint-on-A/restore-on-B at every
    temperature level of the ladder; the migrated result (best value,
    best x, champion trajectory) is bit-exact with the single-device
    uninterrupted run."""
    cfg = _cfg(n_slots=1, n_devices=2)
    victim = _req(0)
    solo = run_standalone(victim, cfg)
    assert solo.levels_run == victim.n_levels > 2
    for level in range(1, victim.n_levels):
        engine = SAServeEngine(cfg)
        engine.submit(victim)
        for _ in range(level):
            engine.tick()
        assert engine.migrate(victim.req_id, to_shard=1)
        res = engine.run(max_ticks=200)[0]
        assert res.migrated_ticks == [level]
        assert res.home_shard == 1
        assert res.preempted_ticks == []     # migration is not preemption
        _assert_bit_exact(res, solo)


def test_migrate_refuses_bad_targets():
    engine = SAServeEngine(_cfg(n_slots=1, n_devices=2))
    assert not engine.migrate(123, 1)        # never submitted
    engine.submit(_req(0))
    assert not engine.migrate(0, 1)          # queued, not active
    engine.tick()                            # -> shard 0
    assert not engine.migrate(0, 0)          # already home
    with pytest.raises(ValueError):
        engine.migrate(0, 7)                 # no such shard
    engine.submit(_req(1, priority=9))       # fills shard 1
    engine.tick()
    assert not engine.migrate(0, 1)          # target full
    assert engine.migrations == 0


def test_migration_then_preemption_compose():
    """A migrated job can still be preempted and resumes bit-exactly."""
    cfg = _cfg(n_slots=1, n_devices=2)
    victim = _req(0)
    engine = SAServeEngine(cfg)
    engine.submit(victim)
    engine.tick()
    assert engine.migrate(0, 1)
    engine.tick()
    assert engine.preempt(0)
    engine.submit(_req(1, priority=50, rho=0.5, T0=8.0))  # steals a slot
    results = {r.req_id: r for r in engine.run(max_ticks=300)}
    res = results[0]
    assert res.n_migrations == 1 and res.n_preemptions == 1
    _assert_bit_exact(res, run_standalone(victim, cfg))


# ----------------------------------------------------- scheduler rebalance
def test_rebalance_defrags_pool_for_wide_request():
    """Fragmented free slots (1 per shard) cannot seat a 2-slot request;
    the planner migrates a narrow job across so the donor shard can."""
    cfg = _cfg(n_slots=2, n_devices=2)
    A, B = _req(0, T0=8.0, rho=0.9), _req(1, T0=8.0, rho=0.9)  # 20 levels
    D = _req(3, T0=8.0, rho=0.9, n_chains=2 * CPS)
    engine = SAServeEngine(cfg)
    engine.submit(A)
    engine.submit(B)
    engine.tick()                  # per-entry least-loaded: A -> 0, B -> 1
    jobs = {j.req.req_id: j for _, j in engine._iter_jobs()}
    assert jobs[0].home_shard != jobs[1].home_shard
    engine.submit(D)               # needs 2; each shard has only 1 free
    engine.tick()
    assert engine.migrations == 1, "rebalance did not fire"
    jobs = {j.req.req_id: j for _, j in engine._iter_jobs()}
    assert 3 in jobs, "wide request was not seated after the migration"
    # No double placement: every live request is resident on exactly one
    # shard, and slot accounting is consistent.
    rids_per_req = [j.req.req_id for _, j in engine._iter_jobs()]
    assert len(rids_per_req) == len(set(rids_per_req))
    results = {r.req_id: r for r in engine.run(max_ticks=400)}
    for req in (A, B, D):
        _assert_bit_exact(results[req.req_id], run_standalone(req, cfg))
    # Drained: no slot leaked on any shard.
    for shard in engine.shards:
        assert shard.pool.n_free == cfg.n_slots
        assert not shard.rids.jobs


def test_migration_budget_zero_disables_rebalance():
    cfg = _cfg(n_slots=2, n_devices=2, migration_budget=0)
    engine = SAServeEngine(cfg)
    engine.submit(_req(0, T0=8.0, rho=0.9))
    engine.submit(_req(1, T0=8.0, rho=0.9))
    engine.tick()                  # one 1-slot job per shard
    engine.submit(_req(3, T0=8.0, rho=0.9, n_chains=2 * CPS))
    engine.tick()
    assert engine.migrations == 0
    assert all(j.req.req_id != 3 for _, j in engine._iter_jobs())
    # It still completes eventually (a whole shard frees up).
    results = {r.req_id: r for r in engine.run(max_ticks=400)}
    assert results[3].completed


def test_overload_fallbacks_fire_only_when_no_shard_fits_full_width():
    """A degrade-class request must not be shrunk by the first-scanned
    shard while another shard could seat it whole — and a preempt-class
    request must not evict while a shard has room."""
    cfg = _cfg(n_slots=2, n_devices=2, scheduler=SchedulerConfig(
        overload="degrade", default_deadline=10.0))
    engine = SAServeEngine(cfg)
    engine.submit(_req(0, priority=5))                     # 1 slot
    engine.submit(_req(1, priority=1, n_chains=2 * CPS))   # 2 slots
    engine.tick()
    jobs = {j.req.req_id: j for _, j in engine._iter_jobs()}
    assert jobs[1].granted_chains == 2 * CPS, \
        "degraded despite full-width room on the other shard"
    assert jobs[0].home_shard != jobs[1].home_shard
    # Preempt flavour: the urgent arrival takes the free shard instead of
    # evicting the resident tenant.
    cfg = _cfg(n_slots=1, n_devices=2,
               scheduler=SchedulerConfig(aging=0.0))
    engine = SAServeEngine(cfg)
    engine.submit(_req(0, priority=0))
    engine.tick()
    engine.submit(_req(1, priority=9, on_overload="preempt"))
    engine.tick()
    assert engine.preemptions == 0 and engine.n_active == 2


def test_preemption_budget_is_per_tick_not_per_shard():
    """The scheduler scans the queue once per shard each tick; the
    preemption budget must bound swap-outs per TICK across all shards,
    not reset per scan."""
    cfg = _cfg(n_slots=1, n_devices=2,
               scheduler=SchedulerConfig(preemption_budget=1, aging=0.0))
    engine = SAServeEngine(cfg)
    engine.submit(_req(0, priority=0))
    engine.submit(_req(1, priority=0))
    engine.tick()                            # one low-prio job per shard
    assert engine.n_active == 2
    engine.submit(_req(2, priority=9, on_overload="preempt"))
    engine.submit(_req(3, priority=9, on_overload="preempt"))
    engine.tick()
    assert engine.preemptions == 1, "budget leaked across shard scans"
    engine.tick()
    assert engine.preemptions == 2           # next tick's budget


# ---------------------------------------------------------- capacity scaling
def test_goodput_scales_with_devices():
    """Acceptance criterion: the same seeded stream completes strictly
    more requests by a fixed horizon on 4 shards than on 1."""
    reqs = [_req(i, T0=8.0, rho=0.5) for i in range(24)]

    def completed_by(n_devices):
        engine = SAServeEngine(_cfg(n_slots=1, n_devices=n_devices))
        engine.run_stream(
            ArrivalProcess.poisson(
                [dataclasses.replace(r) for r in reqs], rate=1.0, seed=7),
            max_ticks=40)
        summary = latency_summary(engine.results, ticks=engine.tick_count,
                                  n_submitted=engine.n_submitted)
        return summary, engine.n_submitted

    (one, n1), (four, n4) = completed_by(1), completed_by(4)
    assert four["completed"] > one["completed"]
    assert four["goodput_req_per_tick"] > one["goodput_req_per_tick"]
    # Terminal accounting stays honest under the horizon cutoff: nothing
    # in flight is counted as rejected.
    assert one["rejected"] == 0 and four["rejected"] == 0
    assert one["completed"] + one["incomplete"] == n1
    assert four["completed"] + four["incomplete"] == n4


def test_sharded_stream_deterministic_and_json_fields():
    """Tick-clock results of a sharded open-loop run reproduce bit-for-bit
    and carry the shard lifecycle fields."""
    def one_run():
        engine = SAServeEngine(_cfg(n_slots=1, n_devices=3))
        reqs = [_req(i, T0=8.0, rho=0.5) for i in range(9)]
        engine.run_stream(ArrivalProcess.poisson(reqs, rate=0.8, seed=3),
                          max_ticks=500)
        return sorted((r.req_id, r.home_shard, tuple(r.migrated_ticks),
                       r.start_tick, r.finish_tick, r.f_best)
                      for r in engine.results)

    r1, r2 = one_run(), one_run()
    assert r1 == r2
    engine = SAServeEngine(_cfg(n_slots=1, n_devices=2))
    engine.submit(_req(0, T0=8.0, rho=0.5))
    d = engine.run(max_ticks=50)[0].to_dict()
    assert {"home_shard", "migrated_ticks", "n_migrations"} <= set(d)


def test_shard_stats_and_run_standalone_single_device():
    engine = SAServeEngine(_cfg(n_slots=1, n_devices=2))
    for i in range(4):
        engine.submit(_req(i, T0=8.0, rho=0.5))
    engine.run(max_ticks=100)
    stats = engine.stats()
    assert stats["devices"] == 2
    assert len(stats["shard_occupancy"]) == 2
    assert all(0.0 <= u <= 1.0 for u in stats["shard_occupancy"])
    # occupancy is the shard mean, so it can never exceed 1 either.
    assert 0.0 < stats["occupancy"] <= 1.0
    # Multi-shard engines have no single pool/rid table.
    with pytest.raises(AttributeError):
        engine.pool
    with pytest.raises(AttributeError):
        engine.rids


def test_oversubscribed_logical_shards_on_one_device():
    """More shards than physical devices round-robin instead of failing
    (the CPU-test path without XLA_FLAGS)."""
    import jax
    n_phys = len(jax.devices())
    engine = SAServeEngine(_cfg(n_slots=1, n_devices=n_phys + 2))
    assert len(engine.shards) == n_phys + 2
    devs = [s.device for s in engine.shards]
    assert devs[0] == devs[n_phys]           # wrapped around
    engine.submit(_req(0, T0=8.0, rho=0.5))
    res = engine.run(max_ticks=50)
    assert res[0].completed


@pytest.mark.skipif(
    len(__import__("jax").devices()) < 2,
    reason="needs >= 2 XLA devices (CI multi-device job sets XLA_FLAGS)")
def test_shards_map_to_distinct_physical_devices():
    """With real devices available, shards 0/1 own different devices and
    cross-device migration stays bit-exact."""
    cfg = _cfg(n_slots=1, n_devices=2)
    engine = SAServeEngine(cfg)
    assert engine.shards[0].device != engine.shards[1].device
    victim = _req(0)
    engine.submit(victim)
    engine.tick()
    engine.tick()
    assert engine.migrate(0, 1)
    res = engine.run(max_ticks=200)[0]
    assert res.home_shard == 1
    _assert_bit_exact(res, run_standalone(victim, cfg))


# ----------------------------------------------------- satellite regressions
def test_run_stream_never_reads_the_wall_clock(monkeypatch):
    """Satellite: run_stream's wall_s once mixed time.time() with the
    perf_counter lifecycle epoch, so a wall-clock adjustment mid-run
    skewed wall_s and every per-second throughput rate.  The engine must
    now draw every wall stamp from the monotonic epoch — i.e. never call
    time.time() at all."""
    import repro.service.engine as eng_mod

    def bomb():
        raise AssertionError("engine consulted the adjustable wall clock")

    monkeypatch.setattr(
        eng_mod, "time",
        types.SimpleNamespace(perf_counter=_time.perf_counter, time=bomb))
    engine = SAServeEngine(_cfg(n_slots=2, n_devices=1))
    results = engine.run_stream(
        ArrivalProcess.batch([_req(0, T0=8.0, rho=0.5)]))
    assert results[0].completed
    assert 0.0 <= engine.wall_s < 600.0
    stats = engine.stats()
    assert stats["sweeps_per_s"] > 0.0
    # wall_s and the lifecycle stamps share one epoch, so the run can
    # never be shorter than the span of events inside it.
    assert engine.wall_s >= results[0].finish_wall - results[0].submit_wall


def test_latency_summary_typed_terminal_accounting():
    """Satellite: 'rejected' counts only the typed 'rejected' terminal;
    work cut off by a --max-ticks horizon surfaces as 'incomplete', and
    preemption counts include evicted-then-rejected requests."""
    done = RequestResult(
        req_id=0, objective="rastrigin", dim=4, x_best=np.zeros(4),
        f_best=1.0, levels_run=3, n_evals=30, submit_tick=0, start_tick=0,
        finish_tick=3, finish_reason="ladder", first_tick=0,
        preempted_ticks=[1], migrated_ticks=[2])
    rejected = RequestResult(
        req_id=1, objective="rastrigin", dim=4, x_best=None,
        f_best=float("inf"), levels_run=1, n_evals=10, submit_tick=0,
        start_tick=-1, finish_tick=5, finish_reason="rejected",
        preempted_ticks=[2, 4], home_shard=-1)
    s = latency_summary([done, rejected], ticks=10, n_submitted=5)
    assert s["completed"] == 1
    assert s["rejected"] == 1                # typed, not a complement
    assert s["incomplete"] == 3              # submitted but no terminal
    assert s["preemptions"] == 3             # includes the rejected one's 2
    assert s["migrations"] == 1
    # Without n_submitted the field is present and zero (closed-loop runs).
    assert latency_summary([done, rejected], ticks=10)["incomplete"] == 0


def test_max_ticks_cutoff_reports_incomplete_not_rejected():
    """End-to-end: a truncated overloaded stream leaves in-flight/queued
    requests as 'incomplete'; 'rejected' stays 0 without a reject policy."""
    engine = SAServeEngine(_cfg(n_slots=1, n_devices=1))
    reqs = [_req(i, T0=8.0, rho=0.9) for i in range(6)]   # 20-level ladders
    engine.run_stream(ArrivalProcess.batch(reqs), max_ticks=5)
    s = latency_summary(engine.results, ticks=engine.tick_count,
                        n_submitted=engine.n_submitted)
    assert s["completed"] == 0 and s["rejected"] == 0
    assert s["incomplete"] == 6
    assert engine.rejections == 0


def test_target_error_requires_registered_optimum():
    """Satellite: target_error on an objective without a known optimum is
    a typed submit-time error, not a mid-tick KeyError that wedges the
    slot."""
    from repro.objectives.families import F_OPT_BY_NAME

    engine = SAServeEngine(_cfg(n_slots=2, n_devices=1))
    saved = F_OPT_BY_NAME.pop("ackley")
    try:
        with pytest.raises(ValueError, match="target_error"):
            engine.submit(_req(0, objective="ackley", target_error=0.5))
        # The engine is not wedged: other work (and the same objective
        # without a target) still serves.
        engine.submit(_req(1, objective="ackley", T0=8.0, rho=0.5))
        engine.submit(_req(2, objective="rastrigin", T0=8.0, rho=0.5,
                           target_error=1000.0))
        results = {r.req_id: r for r in engine.run(max_ticks=100)}
        assert results[1].completed
        assert results[2].finish_reason == "target"
    finally:
        F_OPT_BY_NAME["ackley"] = saved


def test_every_registry_objective_has_an_optimum():
    """The guard can only fire if registry growth forgets an optimum in
    F_OPT_BY_NAME; today the two must agree exactly."""
    from repro.kernels import objective_math as om
    from repro.objectives.families import F_OPT_BY_NAME
    assert set(F_OPT_BY_NAME) == set(om.KID_BY_NAME)
