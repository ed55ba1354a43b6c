"""QAP / permutation-family differentials (PR 9).

The combinatorial path's correctness ladder, bottom-up:

* **instance data integrity**: the built-in QAP instances carry witness
  permutations whose host-side int64 cost equals the recorded best_known;
* **exact arithmetic**: instance entries are small integers, so every
  float32 product/sum in the kernel is exact — the device full cost, the
  delta-carried fx and the host int64 cost agree *bitwise*, not just
  approximately;
* **kernel parity**: the Pallas swap-sweep kernel (interpret mode) is
  bit-identical to the jittable reference oracle, per-block controls and
  packed per-block F/D operands included;
* **serving differentials**: engine == run_standalone for QAP requests at
  macro-K 1 and 4, through preemption, cross-shard migration, drain and
  fleet resize, and when co-batched with continuous tenants in one pool;
* **compile stability**: a mixed continuous+QAP fleet compiles exactly
  one sweep program per family per shape;
* **eager validation** (satellite): family-incompatible request fields
  (pa_ess_ratio, pt/pa methods, wrong dim) raise typed ValueErrors at
  construction;
* **int32 checkpoint/restore** (satellite): the slot pool's
  checkpoint -> restore round-trip is bitwise for permutation blocks.
"""
import dataclasses

import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.qap_sweep import qap_full_cost, qap_sweep_pallas
from repro.objectives import families as fam_mod
from repro.objectives import qap
from repro.service import (EngineConfig, SARequest, SAServeEngine,
                           run_standalone)
from repro.service.slots import SlotPool

CPS = 8


def _req(req_id, instance="syn10", **kw):
    inst = qap.get(instance)
    kw.setdefault("dim", inst.n)
    kw.setdefault("n_chains", CPS)
    kw.setdefault("T0", 30.0)
    kw.setdefault("T_min", 0.5)
    kw.setdefault("rho", 0.55)   # short ladder, like the continuous tests
    kw.setdefault("N", 10)
    kw.setdefault("seed", 100 + req_id)
    return SARequest(req_id=req_id, objective=instance,
                     family="permutation", **kw)


def _creq(req_id, **kw):
    kw.setdefault("objective", "rastrigin")
    kw.setdefault("dim", 4)
    kw.setdefault("n_chains", CPS)
    kw.setdefault("T0", 50.0)
    kw.setdefault("T_min", 1.0)
    kw.setdefault("rho", 0.55)
    kw.setdefault("N", 10)
    return SARequest(req_id=req_id, seed=100 + req_id, **kw)


def _cfg(n_slots=4, **kw):
    return EngineConfig(n_slots=n_slots, chains_per_slot=CPS,
                        use_pallas=False, **kw)


def _assert_bit_exact(res, solo):
    assert res.f_best == solo.f_best
    np.testing.assert_array_equal(res.x_best, solo.x_best)
    assert res.levels_run == solo.levels_run
    assert res.champion_history == solo.champion_history


def _assert_valid_perms(p, n):
    p = np.asarray(p)
    assert p.dtype == np.int32
    np.testing.assert_array_equal(np.sort(p, axis=-1),
                                  np.broadcast_to(np.arange(n, dtype=p.dtype),
                                                  p.shape))


def _rand_perms(n_chains, n, seed):
    r = np.random.default_rng(seed)
    return np.stack([r.permutation(n) for _ in range(n_chains)]
                    ).astype(np.int32)


# ---------------------------------------------------- instance integrity
@pytest.mark.parametrize("name", sorted(qap.INSTANCES))
def test_instance_witness_cost_matches_best_known(name):
    """Each built-in instance's witness permutation reproduces its
    recorded best_known cost under the host int64 evaluator — the data-
    integrity anchor every other test leans on."""
    inst = qap.get(name)
    _assert_valid_perms(np.asarray(inst.p_best, np.int32)[None, :], inst.n)
    assert inst.cost(np.asarray(inst.p_best)) == inst.best_known
    # Zero self-flow / self-distance: the delta formula's diagonal terms
    # vanish, and cost is a pure inter-facility sum.
    assert np.all(np.diag(inst.F) == 0) and np.all(np.diag(inst.D) == 0)
    # Small-integer entries: all products/sums stay exact in float32.
    assert float(np.abs(inst.F).max() * np.abs(inst.D).max() * inst.n ** 2) \
        < 2.0 ** 24
    # A random-permutation cohort never beats the witness.
    costs = inst.cost(_rand_perms(64, inst.n, seed=7))
    assert np.all(costs >= inst.best_known)


@pytest.mark.parametrize("name", sorted(qap.INSTANCES))
def test_device_full_cost_matches_host_bitwise(name):
    """qap_full_cost (the one-hot matmul evaluator chains are seeded
    with) equals the host int64 cost exactly, not approximately."""
    inst = qap.get(name)
    p = _rand_perms(16, inst.n, seed=3)
    f_dev = np.asarray(qap_full_cost(p, inst.F, inst.D))[:, 0]
    np.testing.assert_array_equal(f_dev, inst.cost(p).astype(np.float32))


# ------------------------------------------------------- kernel parity
@pytest.mark.parametrize("name", sorted(qap.INSTANCES))
def test_ref_sweep_delta_fx_is_exact(name):
    """After a reference sweep the delta-carried fx equals a from-scratch
    full recompute AND the host int64 cost, bitwise — the O(n) pairwise-
    exchange delta (arXiv:1208.2675) drifts by exactly nothing."""
    inst = qap.get(name)
    p0 = _rand_perms(16, inst.n, seed=11)
    p1, fx = ref.qap_sweep_ref(p0, inst.F, inst.D, T=5.0, seed=42, step0=0,
                               n_steps=25)
    p1, fx = np.asarray(p1), np.asarray(fx)
    _assert_valid_perms(p1, inst.n)
    np.testing.assert_array_equal(
        fx, np.asarray(qap_full_cost(p1, inst.F, inst.D))[:, 0])
    np.testing.assert_array_equal(fx, inst.cost(p1).astype(np.float32))
    assert not np.array_equal(p0, p1), "sweep accepted no moves at T=5"


@pytest.mark.parametrize("name", sorted(qap.INSTANCES))
def test_pallas_interpret_matches_ref_bitwise(name):
    """The Pallas swap-sweep kernel (interpret mode) is bit-identical to
    the reference oracle under per-block SMEM controls — different T,
    seed and chain_base per block — and per-block packed F/D operands."""
    inst = qap.get(name)
    n_blocks, blk = 2, 8
    p0 = _rand_perms(n_blocks * blk, inst.n, seed=5)
    T = np.asarray([4.0, 1.5], np.float32)
    seeds = np.asarray([9, 9], np.uint32)          # one request, two slots
    step0 = np.asarray([30, 30], np.uint32)
    base = np.asarray([0, blk], np.uint32)         # placement-invariant RNG
    pk, fk = qap_sweep_pallas(p0, inst.F, inst.D, T, seeds, step0,
                              n_steps=20, blk=blk, interpret=True,
                              chain_base=base)
    cidx = (np.repeat(base, blk)
            + np.tile(np.arange(blk, dtype=np.uint32), n_blocks))[:, None]
    pr, fr = ref.qap_sweep_ref(
        p0, inst.F, inst.D, T=np.repeat(T, blk), seed=np.repeat(seeds, blk),
        step0=np.repeat(step0, blk), n_steps=20, cidx=cidx)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(fk), np.asarray(fr))
    _assert_valid_perms(np.asarray(pk), inst.n)


# ------------------------------------------------- serving differentials
@pytest.mark.parametrize("macro_k", [1, 4])
@pytest.mark.parametrize("name", sorted(qap.INSTANCES))
def test_engine_matches_standalone(name, macro_k):
    """Acceptance criterion: a served QAP request is bit-exact versus its
    single-tenant standalone run at macro-K 1 and 4 — f_best, the int32
    champion permutation, and the per-level champion history."""
    cfg = _cfg(macro_k=macro_k)
    req = _req(0, name)
    engine = SAServeEngine(cfg)
    engine.submit(req)
    res = engine.run(max_ticks=200)[0]
    solo = run_standalone(req, cfg)
    _assert_bit_exact(res, solo)
    _assert_valid_perms(res.x_best[None, :], req.dim)
    assert res.x_best.dtype == np.int32


def test_macro_k_is_bit_exact_against_k1():
    """K=4 fused macro-ticks replay the identical trajectory as K=1
    per-level launches for permutation chains (donated int32 buffers)."""
    req = _req(0, "grid12", n_chains=2 * CPS)
    res = {}
    for k in (1, 4):
        engine = SAServeEngine(_cfg(macro_k=k))
        engine.submit(req)
        res[k] = engine.run(max_ticks=200)[0]
    _assert_bit_exact(res[4], res[1])


@pytest.mark.parametrize("macro_k", [1, 4])
def test_mixed_family_cobatch_bit_exact(macro_k):
    """Continuous and QAP tenants share one slot pool and one engine run;
    every champion (float32 and int32 alike) stays bit-exact versus
    standalone."""
    cfg = _cfg(n_slots=6, macro_k=macro_k)
    reqs = [_creq(0), _req(1, "syn10"), _creq(2, objective="ackley"),
            _req(3, "grid12"), _creq(4, objective="schwefel"),
            _req(5, "syn10", seed=321)]
    engine = SAServeEngine(cfg)
    for r in reqs:
        engine.submit(r)
    results = {r.req_id: r for r in engine.run(max_ticks=300)}
    assert len(results) == len(reqs)
    for r in reqs:
        _assert_bit_exact(results[r.req_id], run_standalone(r, cfg))
    assert results[1].x_best.dtype == np.int32
    assert results[0].x_best.dtype == np.float32


def test_preempt_resume_bit_exact_at_every_level():
    """Preempt a QAP tenant at every level of its ladder; the resumed
    trajectory is bit-exact with the uninterrupted run (int32 checkpoint
    blocks + counter-based RNG on logical chain indices)."""
    cfg = _cfg(n_slots=1)
    victim = _req(0, "syn10")
    solo = run_standalone(victim, cfg)
    assert solo.levels_run == victim.n_levels > 2
    for level in range(1, victim.n_levels):
        engine = SAServeEngine(cfg)
        engine.submit(victim)
        for _ in range(level):
            engine.tick()
        assert engine.preempt(victim.req_id)
        filler = _creq(1, priority=50, rho=0.5, T0=8.0)
        engine.submit(filler)    # cross-family filler occupies the slot
        results = {r.req_id: r for r in engine.run(max_ticks=200)}
        assert results[0].preempted_ticks == [level]
        _assert_bit_exact(results[0], solo)
        _assert_bit_exact(results[1], run_standalone(filler, cfg))


def test_drain_and_resize_bit_exact():
    """Drain a QAP tenant's home shard mid-ladder, then (separately)
    resize the fleet under it: the evacuated int32 trajectory matches the
    uninterrupted standalone run bitwise."""
    cfg = _cfg(n_slots=1, n_devices=2, migration_budget=2)
    victim = _req(0, "grid12")
    solo = run_standalone(victim, cfg)

    engine = SAServeEngine(cfg)
    engine.submit(victim)
    engine.tick()
    engine.tick()
    jobs = {j.req.req_id: j for _, j in engine._iter_jobs()}
    home = jobs[0].home_shard
    engine.drain(home)
    res = engine.run(max_ticks=200)[0]
    assert res.migrated_ticks == [2] and res.home_shard != home
    _assert_bit_exact(res, solo)

    engine = SAServeEngine(cfg)
    engine.submit(victim)
    engine.schedule_op(2, lambda: engine.resize(1))
    res = engine.run(max_ticks=200)[0]
    _assert_bit_exact(res, solo)


def test_forced_migration_bit_exact():
    """An operator-forced cross-shard move (checkpoint on A, restore on
    B) leaves the permutation trajectory bit-identical."""
    cfg = _cfg(n_slots=2, n_devices=2, migration_budget=2)
    req = _req(0, "syn10")
    engine = SAServeEngine(cfg)
    engine.submit(req)
    engine.tick()
    jobs = {j.req.req_id: j for _, j in engine._iter_jobs()}
    home = jobs[0].home_shard
    dest = next(s.index for s in engine.live_shards if s.index != home)
    assert engine.migrate(0, dest)
    res = engine.run(max_ticks=200)[0]
    assert res.n_migrations == 1
    _assert_bit_exact(res, run_standalone(req, cfg))


# ---------------------------------------------------- compile stability
def _group_programs_per_family(cfg, reqs):
    """Serve ``reqs`` from a cleared program cache; return the number of
    ``_group_tick`` signatures compiled, each request checked bit-exact
    against its standalone run."""
    from repro.service.engine import _group_tick
    if not all(hasattr(_group_tick, a)
               for a in ("clear_cache", "_cache_size")):
        pytest.skip("jax jit cache introspection API unavailable")
    engine = SAServeEngine(cfg)
    for r in reqs:
        engine.submit(r)
    _group_tick.clear_cache()
    results = {r.req_id: r for r in engine.run(max_ticks=200)}
    compiled = _group_tick._cache_size()   # before the standalone runs
    assert len(results) == len(reqs)
    for r in reqs:
        _assert_bit_exact(results[r.req_id], run_standalone(r, cfg))
    return compiled


def test_one_compiled_program_per_family():
    """A mixed continuous+QAP fleet compiles the one group program once
    per family: the continuous group keeps its runtime-kid dispatch, the
    QAP group its runtime instance operands and int32 states — neither
    family's tenants retrace the other's signature."""
    # Both QAP tenants on one instance (one (family, dim, N) group); three
    # continuous objectives at one (dim, N).
    reqs = [_req(0, "syn10"), _req(1, "syn10", seed=222, T0=20.0),
            _creq(2), _creq(3, objective="ackley"),
            _creq(4, objective="griewank")]
    assert _group_programs_per_family(_cfg(n_slots=6), reqs) == 2


def test_one_fused_program_per_family():
    """Same pin under macro-tick fusion (K=4, donated buffers)."""
    reqs = [_req(0, "syn10"), _req(1, "syn10", seed=222, T0=20.0),
            _creq(2), _creq(3, objective="ackley")]
    assert _group_programs_per_family(_cfg(n_slots=6, macro_k=4), reqs) == 2


# ------------------------------------------------- eager validation (sat)
def test_family_incompatible_fields_fail_at_construction():
    """Satellite: family-incompatible request fields raise typed
    ValueErrors from SARequest.__post_init__, never mid-tick."""
    # Generic coupling check still fires first (sa + ess is wrong in any
    # family); the family-typed error covers the pa-method case.
    with pytest.raises(ValueError, match="pa_ess_ratio"):
        _req(0, pa_ess_ratio=0.5)
    with pytest.raises(ValueError, match="population-annealing control"):
        _req(0, method="pa", pa_ess_ratio=0.5)
    with pytest.raises(ValueError, match="no temperature-rung replica"):
        _req(0, method="pt")
    with pytest.raises(ValueError, match="no temperature-rung replica"):
        _req(0, method="pa")
    with pytest.raises(ValueError, match="does not match QAP instance"):
        _req(0, dim=7)
    with pytest.raises(ValueError, match="not servable by the permutation"):
        SARequest(req_id=0, objective="rastrigin", dim=4, n_chains=CPS,
                  T0=10.0, T_min=1.0, rho=0.5, N=5, family="permutation")
    with pytest.raises(ValueError, match="unknown problem family"):
        dataclasses.replace(_creq(0), family="tsp")
    # Continuous requests reject QAP instance names symmetrically.
    with pytest.raises(ValueError, match="not servable"):
        _creq(0, objective="syn10")


def test_family_accessors_are_consistent():
    """The request's family-derived surface (dtype, kid, f_opt, sampler)
    matches the registered family singletons."""
    q, c = _req(0, "grid12"), _creq(1)
    assert q.prob_family is fam_mod.PERMUTATION
    assert c.prob_family is fam_mod.CONTINUOUS
    assert q.state_dtype == np.int32 and c.state_dtype == np.float32
    assert q.kid == qap.INSTANCE_ID["grid12"]
    assert q.f_opt == qap.get("grid12").best_known
    x0 = q.sample_x0(CPS)
    _assert_valid_perms(x0, q.dim)
    np.testing.assert_array_equal(x0, q.sample_x0(CPS))  # deterministic


# --------------------------------------- int32 checkpoint/restore (sat)
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_slot_checkpoint_restore_roundtrip_is_bitwise_int32(seed):
    """Satellite property test: checkpoint -> release -> restore through
    the slot pool is a bitwise identity for int32 permutation blocks,
    with dtype and chain order preserved and no aliasing between the
    checkpoint and the pool."""
    r = np.random.default_rng(seed)
    n_slots = int(r.integers(2, 5))
    pool = SlotPool(n_slots=4, chains_per_slot=CPS)
    req = _req(0, "grid12", n_chains=n_slots * CPS,
               seed=int(r.integers(0, 2 ** 31)))
    pool.assign(rid=0, req=req)
    before = [b.copy() for b in pool.checkpoint(0)]
    assert all(b.dtype == np.int32 for b in before)
    blocks = pool.checkpoint(0)
    pool.release(0)
    pool.restore(rid=1, blocks=blocks)
    after = pool.checkpoint(1)
    assert len(after) == len(before) == n_slots
    for b0, b1 in zip(before, after):
        assert b1.dtype == np.int32
        np.testing.assert_array_equal(b0, b1)
    # chain_base re-derivation: slot j carries base j*CPS in chain order.
    slots = sorted(pool.slots_of(1), key=lambda s: pool.chain_base[s])
    assert [int(pool.chain_base[s]) for s in slots] == \
        [j * CPS for j in range(n_slots)]


def test_restore_does_not_alias_caller_blocks():
    """restore() defensively copies: mutating the caller's arrays after
    restore must not corrupt pool state (int32 path)."""
    pool = SlotPool(n_slots=2, chains_per_slot=CPS)
    blocks = [_rand_perms(CPS, 12, seed=9)]
    pool.restore(rid=0, blocks=blocks)
    snap = pool.get_block(pool.slots_of(0)[0]).copy()
    blocks[0][:] = -1
    np.testing.assert_array_equal(pool.get_block(pool.slots_of(0)[0]), snap)
