"""Macro-tick fusion differential suite (tentpole gate).

The engine's ``macro_k`` fuses K temperature levels into ONE device
dispatch: an on-device ``fori_loop`` over [masked sweep -> segmented
champion exchange], with per-slot level cursors (dead blocks pass state
through bit-exactly), per-level temperatures threaded as SMEM rows, and
the chain state kept device-resident between launches via donated
ping-pong buffers.  Scheduling decisions (admission, preemption,
migration, drain/resize, retirement) land only on macro-tick boundaries,
and the tick clock stays in LADDER-LEVEL units (one macro-tick advances
it by K), so latency percentiles are comparable across K.

The gate is differential: for every K the engine must be *bit-equal* —
champion history, f_best, x_best, finish reason, evals, and (for aligned
decision schedules) finish tick — to the K=1 engine and to the
``run_standalone`` oracle.  The counter-based RNG keys on logical
(chain, step) coordinates, so fusing levels must not perturb a single
draw; any drift is a correctness bug, not noise.
"""
import numpy as np
import pytest

from repro.service import (ArrivalProcess, EngineConfig, SARequest,
                           SAServeEngine, Telemetry, latency_summary,
                           run_standalone)
import inspect

from repro.service import engine as engine_mod
from repro.service.engine import _group_tick

CPS = 8
K_VALUES = (2, 4, 8)


def _req(req_id, objective="rastrigin", **kw):
    kw.setdefault("dim", 4)
    kw.setdefault("n_chains", CPS)
    kw.setdefault("T0", 50.0)
    kw.setdefault("T_min", 1.0)
    kw.setdefault("rho", 0.8)      # 18-level ladder
    kw.setdefault("N", 10)
    return SARequest(req_id=req_id, objective=objective,
                     seed=100 + req_id, **kw)


def _cfg(k=1, n_devices=1, **kw):
    kw.setdefault("n_slots", 4)
    return EngineConfig(chains_per_slot=CPS, n_devices=n_devices,
                        macro_k=k, use_pallas=False, **kw)


#: Mixed objectives, dims and footprints — one 2-slot request so the
#: fused path sees multi-block tenants and a pad block (5 blocks -> 8).
MIX = [
    dict(objective="rastrigin"),
    dict(objective="ackley", dim=8),
    dict(objective="griewank", n_chains=2 * CPS),
    dict(objective="schwefel"),
]


def _mix(**extra):
    return [_req(i, **{**kw, **extra}) for i, kw in enumerate(MIX)]


def _serve(reqs, k, n_devices=2, ops=None, telemetry=None, **cfg_kw):
    cfg = _cfg(k=k, n_devices=n_devices, **cfg_kw)
    engine = SAServeEngine(cfg, telemetry=telemetry)
    for r in reqs:
        engine.submit(r)
    if ops is not None:
        ops(engine)
    results = {r.req_id: r for r in engine.run(max_ticks=2000)}
    return results, engine, cfg


def _assert_bit_equal(a, b, *, ticks=True):
    assert a.keys() == b.keys()
    for rid in a:
        ra, rb = a[rid], b[rid]
        assert ra.champion_history == rb.champion_history, rid
        assert ra.f_best == rb.f_best, rid
        np.testing.assert_array_equal(ra.x_best, rb.x_best)
        assert ra.finish_reason == rb.finish_reason, rid
        assert ra.levels_run == rb.levels_run, rid
        assert ra.n_evals == rb.n_evals, rid
        if ticks:
            assert ra.finish_tick == rb.finish_tick, rid
            assert ra.first_tick == rb.first_tick, rid


# ------------------------------------------------------------ equivalence
@pytest.mark.parametrize("k", K_VALUES)
def test_fused_engine_bit_equal_to_k1_and_standalone(k):
    """The headline differential: mixed objectives/dims/footprints on a
    2-shard fleet — every K produces the identical result set, including
    ladder-level finish ticks, and matches the standalone oracle."""
    base, _, _ = _serve(_mix(), k=1)
    fused, _, cfg = _serve(_mix(), k=k)
    _assert_bit_equal(base, fused)
    for req in _mix():
        solo = run_standalone(req, cfg)
        assert fused[req.req_id].f_best == solo.f_best
        assert fused[req.req_id].champion_history == solo.champion_history


def test_k_exceeding_remaining_levels_truncates_cleanly():
    """K larger than the whole ladder: the fused program still runs K
    slots of work on device but only `n_levels` are live — results and
    the ladder-level clock are identical to K=1."""
    short = [_req(0, T0=4.0, T_min=1.0, rho=0.5),       # 2-level ladder
             _req(1, objective="ackley", T0=4.0, T_min=1.0, rho=0.5)]
    base, eng1, _ = _serve(short, k=1)
    fused, eng8, cfg = _serve(short, k=8)
    _assert_bit_equal(base, fused)
    assert fused[0].levels_run == short[0].n_levels == 2
    assert eng8.tick_count == eng1.tick_count
    for req in short:
        solo = run_standalone(req, cfg)
        assert fused[req.req_id].champion_history == solo.champion_history


def test_k1_degenerate_path_compiles_no_fused_programs():
    """macro_k=1 compiles no program of its own: K=1 and K=4 both run the
    one ``_group_tick`` (one compiled entry per group shape for each k),
    and K=1 keeps the state residency: after a launch every active slot
    holds a device ref into its group's cached output buffer, and the
    dispatch cache is armed."""
    if not (hasattr(_group_tick, "clear_cache")
            and hasattr(_group_tick, "_cache_size")):
        pytest.skip("kernel cache introspection unavailable")
    _group_tick.clear_cache()
    cfg = _cfg(k=1, n_devices=2)
    engine = SAServeEngine(cfg)
    for r in _mix():
        engine.submit(r)
    for _ in range(2):
        engine.tick()
        for shard in engine.shards:
            bufs = {id(e["buf"]) for e in shard.group_cache.values()}
            assert len(bufs) == len(shard.group_cache) >= 1
            for job in shard.rids.jobs.values():
                for s in job.slots:
                    ref = shard.pool.device_ref(s)
                    assert ref is not None and id(ref.buf) in bufs
    engine.run(max_ticks=2000)
    at_k1 = _group_tick._cache_size()
    assert at_k1 >= 1
    _, engine, _ = _serve(_mix(), k=4)
    assert _group_tick._cache_size() == 2 * at_k1
    assert any(s.group_cache for s in engine.shards)


@pytest.mark.parametrize("k", [1, 4])
def test_launch_uploads_one_partner_row_per_parity_in_use(k, monkeypatch):
    """The controls a launch hands the group program: ``k`` temperature
    rows, one level cursor per block, and ``min(k, 2)`` rows of PT
    partners and pair keys — one row at K=1, where the program never
    reads a second parity."""
    seen = []

    def spy(*args, **kwargs):
        bound = inspect.signature(_group_tick.__wrapped__).bind(*args,
                                                                **kwargs)
        seen.append({name: np.shape(v)
                     for name, v in bound.arguments.items()
                     if name not in ("family", "fam_ops")})
        return _group_tick(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "_group_tick", spy)
    # One 2-slot request plus a 1-slot one: 3 blocks, padded to 4.
    _serve([_req(0, n_chains=2 * CPS), _req(1, method="pt")], k=k,
           n_devices=1)
    assert seen
    n_padded, rows = 4, min(k, 2)
    for shapes in seen:
        assert shapes["partner"] == shapes["pairlo"] == (rows, n_padded * CPS)
        assert shapes["T_lvls"] == shapes["dbeta_lvls"] == (k, n_padded)
        assert shapes["levels_blk"] == (n_padded,)


# ----------------------------------------------------- boundary decisions
@pytest.mark.parametrize("k", K_VALUES)
def test_preemption_resize_drain_at_macro_boundaries(k):
    """Operator actions scripted at K-aligned ticks land on the same
    macro-tick boundary at every K, so even lifecycle tick stamps match
    the K=1 engine bit-for-bit."""
    def ops(engine):
        engine.schedule_op(8, lambda: engine.preempt(0))
        engine.schedule_op(8, lambda: engine.resize(3))
        engine.schedule_op(16, lambda: engine.drain(1))

    base, _, _ = _serve(_mix(), k=1, ops=ops)
    fused, engine, cfg = _serve(_mix(), k=k, ops=ops)
    _assert_bit_equal(base, fused)
    for rid in fused:
        for t in fused[rid].preempted_ticks + fused[rid].migrated_ticks:
            assert t % k == 0, "decision off a macro-tick boundary"
    for req in _mix():
        sched = [(lvl, to) for lvl, _frm, to
                 in fused[req.req_id].shrink_events]
        solo = run_standalone(req, cfg, shrink_schedule=sched)
        assert fused[req.req_id].champion_history == solo.champion_history


@pytest.mark.parametrize("k", K_VALUES)
def test_budget_and_target_stops_mid_macro_tick(k):
    """Terminal reasons that fire *inside* a macro-tick: a max_evals
    budget whose level count is not a multiple of K, and a target-error
    stop at an unpredictable level.  The host truncates retroactively —
    counted levels, evals and the ladder-level finish tick must all
    match K=1 exactly."""
    reqs = [
        _req(0, max_evals=3 * 10 * CPS),              # 3 levels by budget
        _req(1, objective="ackley", target_error=10.0),  # fires at level 9
        _req(2, n_chains=2 * CPS,
             max_evals=5 * 10 * 2 * CPS + 1),          # 6 levels by budget
    ]
    base, _, _ = _serve(reqs, k=1)
    fused, _, _ = _serve(reqs, k=k)
    _assert_bit_equal(base, fused)
    assert fused[0].finish_reason == "budget"
    assert fused[0].levels_run == 3
    assert fused[1].finish_reason == "target"
    assert fused[1].levels_run == 9        # not K-aligned for any tested K


def test_open_loop_stream_bit_exact_at_k4():
    """Open-loop Poisson arrivals admit on macro-tick boundaries; the
    trajectories (placement- and timing-invariant by construction) still
    match the standalone oracle for every completed request."""
    reqs = _mix()
    cfg = _cfg(k=4, n_devices=2, n_slots=2)
    engine = SAServeEngine(cfg)
    results = {r.req_id: r for r in engine.run_stream(
        ArrivalProcess.poisson(reqs, rate=0.5, seed=3), max_ticks=2000)}
    assert sorted(results) == [r.req_id for r in reqs]
    for req in reqs:
        solo = run_standalone(req, cfg)
        assert results[req.req_id].f_best == solo.f_best
        assert results[req.req_id].champion_history == solo.champion_history


# ------------------------------------------------- double-buffer dispatch
@pytest.mark.parametrize("k", (1, 4))
def test_double_buffer_flips_and_cache_hits_on_stable_membership(k):
    """Steady state: each launch donates the previous output buffer back
    in (ping-pong), so the cached buffer identity changes every macro-
    tick and every slot ref points into the *current* cache buffer."""
    reqs = [_req(0), _req(1, objective="ackley")]
    cfg = _cfg(k=k, n_slots=2)
    tel = Telemetry()
    engine = SAServeEngine(cfg, telemetry=tel)
    for r in reqs:
        engine.submit(r)
    bufs = []
    for _ in range(3):
        engine.tick()
        shard = engine.shards[0]
        (key,) = shard.group_cache
        entry = shard.group_cache[key]
        bufs.append(id(entry["buf"]))
        for s in range(cfg.n_slots):
            ref = shard.pool.device_ref(s)
            assert ref is not None and ref.buf is entry["buf"]
    assert len(set(bufs)) == 3, "output buffer never flipped"
    buf = tel.registry["sa_state_buffer_total"]
    assert (buf.value("repack"), buf.value("hit")) == (1, 2)
    results = {r.req_id: r for r in engine.run(max_ticks=2000)}
    for req in reqs:
        solo = run_standalone(req, cfg)
        assert results[req.req_id].champion_history == solo.champion_history


@pytest.mark.parametrize("k", (1, 4))
def test_membership_change_invalidates_dispatch_cache(k):
    """A preemption between macro-ticks repacks from host (the checkpoint
    materialized the device ref); the resumed trajectory is still
    bit-exact, so the cache-miss path reads back exactly the state the
    donated buffer held."""
    reqs = [_req(0), _req(1, objective="griewank")]
    cfg = _cfg(k=k, n_slots=2)
    engine = SAServeEngine(cfg)
    for r in reqs:
        engine.submit(r)
    engine.tick()
    engine.preempt(0)            # materializes + frees slot 0's ref
    assert engine.shards[0].pool.device_ref(0) is None
    results = {r.req_id: r for r in engine.run(max_ticks=2000)}
    for req in reqs:
        solo = run_standalone(req, cfg)
        assert results[req.req_id].champion_history == solo.champion_history
        assert results[req.req_id].f_best == solo.f_best


def test_k1_join_and_retire_mid_run_repack_reads_each_buffer_once():
    """Churn at K=1 in one (dim, N) group: a request joins mid-run and
    another retires.  Each change is a cache miss whose repack reads the
    group's one source buffer back in a single transfer (not one per
    slot), every other launch reuses the buffer, and every trajectory
    stays bit-exact."""
    reqs = [_req(0, n_chains=2 * CPS),                         # 18 levels
            _req(1, objective="ackley", T0=6.0, rho=0.5),      # 3 levels
            _req(2, objective="griewank")]                     # joins
    assert reqs[1].n_levels == 3
    cfg = _cfg(k=1, n_slots=4)
    tel = Telemetry()
    engine = SAServeEngine(cfg, telemetry=tel)
    engine.submit(reqs[0])
    engine.submit(reqs[1])
    shard = engine.shards[0]
    pool = shard.pool
    buf = tel.registry["sa_state_buffer_total"]

    def source():
        (entry,) = shard.group_cache.values()
        return entry["buf"]

    engine.tick()                # 3 blocks padded to 4: packed, nothing read
    assert (buf.value("repack"), buf.value("hit")) == (1, 0)
    assert pool.bytes_read == 0
    engine.submit(reqs[2])
    src = source()
    engine.tick()                # request 2 joins: 4 blocks, same padding
    assert (buf.value("repack"), buf.value("hit")) == (2, 0)
    assert pool.bytes_read == src.nbytes
    engine.tick()                # stable: request 1 ends its ladder here
    assert (buf.value("repack"), buf.value("hit")) == (2, 1)
    assert [r.req_id for r in engine.results] == [1]
    src, read0 = source(), pool.bytes_read
    engine.tick()                # request 2's slot moves up a row
    assert (buf.value("repack"), buf.value("hit")) == (3, 1)
    assert pool.bytes_read - read0 == src.nbytes
    assert tel.registry["sa_state_bytes_total"].value("d2h") == \
        pool.bytes_read
    results = {r.req_id: r for r in engine.run(max_ticks=2000)}
    # Request 0 ends a level before request 2, whose block then moves to
    # row 0: one more repack, and every other launch a hit.
    assert buf.value("repack") == 4
    assert buf.value("hit") == engine.group_launches - 4
    for req in reqs:
        solo = run_standalone(req, cfg)
        assert results[req.req_id].champion_history == solo.champion_history
        assert results[req.req_id].f_best == solo.f_best
        np.testing.assert_array_equal(results[req.req_id].x_best,
                                      solo.x_best)


# --------------------------------------------------- ladder-level latency
def test_latency_summary_units_invariant_across_k():
    """Satellite: the tick clock is measured in ladder levels at any K,
    so p50/p99 queueing delay, TTFT and end-to-end latency of the same
    seeded closed-loop batch are *identical* numbers at K=1 and K=4 —
    fusing levels is a wall-clock optimization, never a unit change."""
    def summarize(k):
        results, engine, _ = _serve(_mix(), k=k, n_devices=1)
        return latency_summary(list(results.values()),
                               ticks=engine.tick_count,
                               n_submitted=engine.n_submitted)

    s1, s4 = summarize(1), summarize(4)
    for key in ("completed", "rejected", "incomplete",
                "queue_delay_p50", "queue_delay_p99",
                "ttft_p50", "ttft_p99", "latency_p50", "latency_p99",
                "goodput_req_per_tick"):
        assert s1[key] == pytest.approx(s4[key], nan_ok=True), key


def test_tick_clock_advances_by_k_only_when_active():
    """tick_count counts ladder levels: K per active macro-tick, 1 per
    idle tick — so sa_ticks_total and goodput denominators stay on the
    same axis as the K=1 engine."""
    engine = SAServeEngine(_cfg(k=4, n_slots=2))
    engine.tick()                              # idle: no active slots
    assert engine.tick_count == 1
    engine.submit(_req(0))
    engine.tick()
    assert engine.tick_count == 5              # 1 idle + 4 fused levels


# ------------------------------------------------------------- telemetry
def test_telemetry_on_is_bit_exact_at_k4():
    tel = Telemetry()
    plain, _, _ = _serve(_mix(), k=4)
    traced, engine, _ = _serve(_mix(), k=4, telemetry=tel)
    _assert_bit_equal(plain, traced)
    snap = tel.registry.snapshot()
    assert snap["sa_ticks_total"]["series"][""] == engine.tick_count


# ----------------------------------------------------------------- config
def test_macro_k_validation():
    with pytest.raises(ValueError):
        EngineConfig(n_slots=2, chains_per_slot=CPS, macro_k=0)


def test_run_standalone_uses_engine_macro_k():
    """run_standalone inherits cfg.macro_k, so the oracle itself runs the
    fused path — and still matches a K=1 standalone run bit-for-bit."""
    req = _req(0, n_chains=2 * CPS)
    # Shrink schedules replay at macro-tick boundaries, so the level must
    # be K-aligned — which engine-recorded shrink_events always are.
    sched = [(8, CPS)]
    solo_1 = run_standalone(req, _cfg(k=1), shrink_schedule=sched)
    solo_4 = run_standalone(req, _cfg(k=4), shrink_schedule=sched)
    assert solo_1.champion_history == solo_4.champion_history
    assert solo_1.f_best == solo_4.f_best
